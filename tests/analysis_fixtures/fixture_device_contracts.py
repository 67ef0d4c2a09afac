"""Seeded device-contract violations for the DC6xx pass.

This file is PARSED by tests, never imported.  Each function/class pins
one rule shape with its exact code/symbol/line asserted in
tests/test_static_analysis.py — change a line here and the test's
line-anchor lookup follows it, but the (code, symbol) pairs are the
contract.  The *_ok shapes pin the exemptions: the pass must stay
silent on them.
"""

from functools import lru_cache

import jax
import jax.numpy as jnp


# -- jit-factory chain mirroring _loop_runner/_loop_runner_for ------------


@lru_cache(maxsize=None)
def _fixture_runner(chunk: int):
    @jax.jit
    def run(dev, state):
        return state * chunk

    return jax.jit(run, donate_argnums=(1,))


def _fixture_runner_for(chunk: int):
    return _fixture_runner(int(chunk))


class FixtureLoop:
    def __init__(self, chunk: int):
        self._dev = jnp.ones((4,))
        self._state = jnp.zeros((4,))
        self._loop = _fixture_runner_for(int(chunk))

    # DC601: donated carry read after dispatch, before the rebind
    def dispatch_bad(self):
        out = self._loop(self._dev, self._state)
        stale = self._state  # buffer already donated
        self._state = out
        return stale

    # exemption: rebind first, then read — clean
    def dispatch_ok(self):
        out = self._loop(self._dev, self._state)
        self._state = out
        return self._state

    # DC601 one-hop: a callee invoked in the window reads the donated attr
    def dispatch_callee_bad(self):
        out = self._loop(self._dev, self._state)
        self._peek()
        self._state = out

    def _peek(self):
        return self._state

    # DC602: unsanctioned host materialization of a device value
    def sync_bad(self):
        n = int(jnp.sum(self._state))
        return n

    # exemption: sanctioned site with a reason
    def sync_ok(self):
        # device: sync — fixture-sanctioned control read
        n = int(jnp.sum(self._state))
        return n


# -- DC603 shapes ---------------------------------------------------------


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _sticky_pad(axis, n):
    return n


def _pow2_width(n: int, lo: int) -> int:
    return max(lo, n)


def pad_bad(n: int) -> int:
    return _pad_to(n, 8)


def pad_ok_sticky(n: int) -> int:
    return _sticky_pad("nodes", _pad_to(n, 8))


def pad_ok_annotated(n: int) -> int:
    return _pad_to(n, 8)  # device: static — fixture-sanctioned


def width_bad(n: int) -> int:
    return _pow2_width(n, 8)


def width_ok(n: int) -> int:
    return _pow2_width(n, 8)  # device: static — fixture-sanctioned


def factory_call_bad(static):
    run = _fixture_runner(static.chunk)
    return run(jnp.ones((4,)), jnp.zeros((4,)))


def factory_call_ok(static):
    run = _fixture_runner(int(static.chunk))
    return run(jnp.ones((4,)), jnp.zeros((4,)))


# -- DC604 shapes ---------------------------------------------------------


def fixture_schedule(node_info_map, pods):
    work_map = dict(node_info_map)

    def mutable_info(name):
        fresh = work_map[name].clone()
        work_map[name] = fresh
        return fresh

    def apply_ok(name, pod):
        info = mutable_info(name)
        info.add_pod(pod)

    def apply_bad(name, pod):
        raw = work_map.get(name)
        raw.add_pod(pod)
        work_map[name].remove_pod(pod)
        raw.node = None

    def place_ok(name, pods):
        fresh = mutable_info(name)
        fresh.add_pods_counted(pods, None, None, (), ())

    def place_bad(name, pods):
        stale = work_map.get(name)
        stale.add_pods_counted(pods, None, None, (), ())

    for pod in pods:
        apply_ok(pod, pod)
        apply_bad(pod, pod)
    place_ok(pods[0], pods)
    place_bad(pods[0], pods)
    return work_map


# -- DC605 shapes ---------------------------------------------------------


def stale_sync_annotation(x):
    # device: sync — nothing materializes on this line or the next
    y = x + 1
    return y


def reasonless_sync(dev):
    # device: sync
    n = int(jnp.sum(dev))
    return n


def stale_static_annotation(x):
    # device: static
    return x + 1
