"""Ways to break the timed path underneath a rehearsal run, one for each
fault a one-chip cell can have.  ``python -m tests.benchmark.faults NAME
<benchmark.run arguments>`` is ``benchmark.run`` with the fault planted; the
comparison that decides ``correct`` has to see it."""

import sys


def answer_altered(sched, backend) -> None:
    """Answers altered where they are produced: within each segment's
    results, neighbouring bound pods swap their nodes."""
    schedule_batch = backend.schedule_batch

    def altered(pods, snapshot, pctx, on_segment=None, **kw):
        def swapped(entries):
            entries = list(entries)
            bound = [i for i, e in enumerate(entries) if e[1] is not None]
            for a, b in zip(bound[::2], bound[1::2]):
                ea, eb = entries[a], entries[b]
                entries[a] = (ea[0], eb[1], ea[2], ea[3])
                entries[b] = (eb[0], ea[1], eb[2], eb[3])
            return on_segment(entries)

        return schedule_batch(pods, snapshot, pctx, on_segment=swapped, **kw)

    backend.schedule_batch = altered


def half_batch_left_out(sched, backend) -> None:
    """Half of each drained batch never reaches the scheduler."""
    drain = sched.queue.drain

    def halved(max_n=None):
        return drain(max_n)[::2]

    sched.queue.drain = halved


def state_unchanged(sched, backend) -> None:
    """A step that returns its state unchanged: the tie counter a wave
    leaves behind is thrown away, so every wave starts from the first's."""
    schedule_batch = backend.schedule_batch

    def stateless(pods, snapshot, pctx, **kw):
        before = backend.algorithm._round_robin
        out = schedule_batch(pods, snapshot, pctx, **kw)
        backend.algorithm._round_robin = before
        return out

    backend.schedule_batch = stateless


FAULTS = {f.__name__: f for f in (answer_altered, half_batch_left_out, state_unchanged)}

if __name__ == "__main__":
    from benchmark import run

    code = run.main(sys.argv[2:], hooks={"after_wiring": FAULTS[sys.argv[1]]})
    sys.stdout.flush()
    sys.stderr.flush()
    import os

    os._exit(code)
