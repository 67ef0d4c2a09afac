"""kube-scheduler daemon (reference ``plugin/cmd/kube-scheduler/app/
server.go:67 Run``, leader election ``:133``).

    python -m kubernetes_tpu.scheduler --apiserver http://host:6443 \
        [--leader-elect] [--backend tpu|oracle] [--batch-interval 0.05] \
        [--policy-config-file policy.json]
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import threading

from ..daemon import install_signal_stop, remote_clientset, run_with_leader_election


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubernetes_tpu.scheduler")
    ap.add_argument("--apiserver", default=None)
    ap.add_argument("--token", default=None)
    ap.add_argument("--kubeconfig", default=None,
                    help="connection document from the kubeadm kubeconfig "
                    "phase (server + CA pin + client cert); --apiserver/"
                    "--token override its fields")
    ap.add_argument("--leader-elect", action="store_true")
    # SUPPRESS so explicit flags can be told apart from defaults when a
    # --config file is layered underneath (flag > file > default)
    ap.add_argument("--backend", choices=["tpu", "oracle"], default=argparse.SUPPRESS)
    ap.add_argument("--batch-interval", type=float, default=argparse.SUPPRESS,
                    help="seconds to coalesce pending pods before a TPU batch")
    ap.add_argument("--policy-config-file", default=argparse.SUPPRESS)
    ap.add_argument("--scheduler-name", default=argparse.SUPPRESS)
    ap.add_argument("--feature-gates", default="")
    ap.add_argument("--config", default=None,
                    help="SchedulerConfiguration YAML (componentconfig)")
    ap.add_argument("--healthz-port", type=int, default=-1,
                    help="serve /healthz + /metrics (reference :10251); "
                         "-1 = off, 0 = ephemeral")
    ap.add_argument("--trace", action="store_true",
                    help="enable wave tracing + the flight recorder; "
                         "exported at /debug/traces (Chrome trace-event "
                         "JSON) and /debug/flightrecorder on the healthz "
                         "port")
    ap.add_argument("--trace-dump-dir", default=None,
                    help="with --trace: also write each flight-recorder "
                         "dump as a JSON file under this directory")
    ap.add_argument("--timeseries", action="store_true",
                    help="scrape the metrics registry into in-process "
                         "time-series rings (served at /debug/timeseries) "
                         "and run the burn-rate SLO monitor — a breach "
                         "fires the flight recorder")
    ap.add_argument("--timeseries-interval", type=float, default=1.0,
                    help="scrape cadence in seconds (with --timeseries)")
    ap.add_argument("--telemetry-sink", default=None,
                    help="ship flight dumps + time-series deltas off-box: "
                         "an http(s):// collector URL (the apiserver's "
                         "/telemetry ingest) or a JSON-lines file path; "
                         "implies --timeseries")
    args = ap.parse_args(argv)
    from ..utils.features import SchedulerConfiguration, load_component_config

    cfg = (load_component_config(SchedulerConfiguration, args.config)
           if args.config else SchedulerConfiguration())
    # flag > config file > dataclass default
    for attr in ("scheduler_name", "backend", "batch_interval", "policy_config_file"):
        if not hasattr(args, attr):
            setattr(args, attr, getattr(cfg, attr) or (None if attr == "policy_config_file" else getattr(cfg, attr)))
    args.leader_elect = args.leader_elect or cfg.leader_elect
    if args.config and cfg.feature_gates:
        from ..utils.features import DEFAULT_FEATURE_GATES

        DEFAULT_FEATURE_GATES.set_from_map(cfg.feature_gates)
    if args.feature_gates:
        from ..utils.features import DEFAULT_FEATURE_GATES

        DEFAULT_FEATURE_GATES.set_from_string(args.feature_gates)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    if not args.apiserver and not args.kubeconfig:
        ap.error("one of --apiserver or --kubeconfig is required")
    cs = remote_clientset(args.apiserver, args.token,
                          kubeconfig=args.kubeconfig)

    # health BEFORE leader election: a standby must still answer its
    # liveness probe or the supervisor kills a healthy HA peer.  The
    # metrics registry appears once the payload constructs the scheduler.
    from ..daemon import serve_health

    metrics_holder: dict = {}

    class _LazyRegistry:
        def expose(self):
            reg = metrics_holder.get("registry")
            return reg.expose() if reg is not None else "# standby\n"

    if args.trace:
        from ..utils import tracing

        tracing.enable(dump_dir=args.trace_dump_dir)
        logging.info("wave tracing enabled (flight recorder armed)")

    health = serve_health(args.healthz_port, _LazyRegistry())
    if health is not None:
        logging.info("healthz/metrics%s on :%d",
                     " + /debug/traces" if args.trace else "",
                     health.local_port)

    def run(payload_stop: threading.Event) -> None:
        from .generic_scheduler import GenericScheduler
        from .scheduler import Scheduler

        algo = GenericScheduler()
        if args.policy_config_file:
            from .policy import load_policy_file

            algo = load_policy_file(args.policy_config_file)
        backend = None
        if args.backend == "tpu":
            import jax

            from ..ops import TPUBatchBackend

            backend = TPUBatchBackend(algorithm=algo)
            # the first JAX touch of this process: a standby never gets
            # here, so only the leader holds the accelerator
            devices = jax.devices()
            logging.info("backend tpu: platform=%s device_kind=%s devices=%d",
                         devices[0].platform, devices[0].device_kind,
                         len(devices))
        sched = Scheduler(cs, algorithm=algo, backend=backend,
                          scheduler_name=args.scheduler_name)
        metrics_holder["registry"] = sched.metrics.registry
        if args.timeseries or args.telemetry_sink:
            from ..daemon import enable_continuous_telemetry

            enable_continuous_telemetry(
                sched.metrics.registry,
                interval_s=args.timeseries_interval,
                sink_spec=args.telemetry_sink)
            logging.info("continuous telemetry enabled (scrape %.2fs%s)",
                         args.timeseries_interval,
                         f", sink={args.telemetry_sink}"
                         if args.telemetry_sink else "")
        sched.start(manual=False)  # threaded informers + event sink
        logging.info("scheduler running (backend=%s)", args.backend)
        while not payload_stop.is_set():
            if backend is not None:
                # continuous service mode: drain as pods arrive under the
                # min-batch/max-wait policy (batch_interval caps the
                # accumulation window); returns when payload_stop is set
                bound = sched.run_batch_loop(
                    # one full kernel segment ends the accumulation early;
                    # otherwise the window is batch_interval, matching the
                    # old fixed-interval coalescing
                    min_batch=backend.max_segment_pods,
                    max_wait=args.batch_interval, stop=payload_stop,
                    poll_interval=min(0.05, args.batch_interval))
                if bound:
                    logging.info("batch loop: %d bound", bound)
            else:
                if not sched.schedule_one(timeout=0.2, async_bind=True):
                    continue
        sched.informers.stop_all()
        sched.broadcaster.stop()
        if args.timeseries or args.telemetry_sink:
            from ..utils import telemetry, timeseries

            timeseries.disable()
            telemetry.disable()  # final drain before exit
        if backend is not None and not stop.is_set():
            # stopped by a lost lease, not a signal: an accelerator belongs
            # to one process at a time, so a deposed leader that re-entered
            # the acquire loop would keep the chip its successor needs —
            # exit instead, as the reference does (server.go:133
            # OnStoppedLeading)
            logging.error("lost the lease while holding the accelerator; "
                          "exiting so the next leader can take it")
            lost_with_device.set()
            stop.set()

    lost_with_device = threading.Event()
    stop = install_signal_stop()
    try:
        run_with_leader_election(
            cs, "kube-scheduler", f"scheduler-{os.getpid()}", run, stop,
            leader_elect=args.leader_elect,
        )
    finally:
        if health is not None:
            health.stop()
    return 1 if lost_with_device.is_set() else 0


if __name__ == "__main__":
    sys.exit(main())
