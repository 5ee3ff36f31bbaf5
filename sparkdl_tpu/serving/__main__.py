"""CLI for the serving layer.

    python -m sparkdl_tpu.serving serve   [--port P] [--budget-mb N]
                                          [--max-batch N]
    python -m sparkdl_tpu.serving gateway [--workers N] [--port P]
                                          [--gang-dir D] [--loader M:F]
                                          [--budget-mb N] [--max-batch N]
    python -m sparkdl_tpu.serving worker  --rank R --gang-dir D
                                          [--port P] [--loader M:F]
                                          [--budget-mb N] [--max-batch N]
                                          [--heartbeat-interval S]
    python -m sparkdl_tpu.serving models

``serve`` binds the single-process HTTP front-end over the named-model
registry (port from ``--port`` or ``SPARKDL_SERVE_PORT``, default 8000)
and blocks until interrupted. ``gateway`` runs the supervised
multi-worker tier (docs/RESILIENCE.md "Serving gang"): N ``worker``
subprocesses under the GangSupervisor behind one health-checked routing
door. ``worker`` is the gang member the gateway launches — the same
Router/residency/server stack plus the gang protocol: a
generation-tagged port file + heartbeats in ``--gang-dir``, and a
SIGTERM handler that drains (admission 503s, accepted work completes)
before exiting 0. ``models`` prints the registry with per-model
device-memory estimates — no backend touched beyond shape tracing.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import signal
import sys
import threading
import time
from typing import List, Optional


def _resolve_loader(spec: Optional[str]):
    """``pkg.mod:attr`` -> the loader callable, or None for the
    named-model registry default."""
    if not spec:
        return None
    mod_name, sep, attr = spec.partition(":")
    if not sep or not attr:
        raise SystemExit(
            f"--loader {spec!r}: expected 'pkg.mod:function'"
        )
    fn = getattr(importlib.import_module(mod_name), attr, None)
    if not callable(fn):
        raise SystemExit(
            f"--loader {spec!r}: {attr!r} is not a callable in {mod_name!r}"
        )
    return fn


def _serving_env_defaults() -> None:
    """Serving-process feeder defaults (explicit env still wins): owners
    never idle-exit between bursts, and the stream registry is sized
    for model x rung x geometry populations instead of the batch
    engine's one-geometry-per-model shape."""
    os.environ.setdefault("SPARKDL_FEEDER_IDLE_S", "0")
    os.environ.setdefault("SPARKDL_MAX_FEEDERS", "32")


def _write_port_file(gang_dir: str, rank: int, port: int, generation: int):
    """Publish the worker's bound port for the gateway, atomically
    (tmp + rename, the heartbeat discipline) and generation-tagged so a
    relaunched gateway never routes to a dead incarnation's port."""
    from sparkdl_tpu.serving.gateway import port_file

    path = port_file(gang_dir, rank)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(
            {
                "rank": rank,
                "port": port,
                "pid": os.getpid(),
                "generation": generation,
            },
            f,
        )
    os.replace(tmp, path)


def _worker_main(args) -> int:
    """One serving gang member. Lifecycle: bind ephemeral -> publish
    port -> heartbeat -> serve until SIGTERM -> drain (admission 503s
    with Retry-After, queued + in-flight complete, feeders close) ->
    exit 0. The supervisor TERMs before it KILLs, so the drain window
    is the graceful half of every gang restart."""
    _serving_env_defaults()
    from sparkdl_tpu.runtime import knobs
    from sparkdl_tpu.runtime.heartbeat import Heartbeat
    from sparkdl_tpu.serving.router import Router
    from sparkdl_tpu.serving.server import ServingServer

    rank = int(args.rank)
    os.environ.setdefault("SPARKDL_OBS_RANK", str(rank))
    generation = knobs.get_int("SPARKDL_GANG_GENERATION") or 0
    os.makedirs(args.gang_dir, exist_ok=True)

    if args.budget_mb is not None:
        os.environ["SPARKDL_SERVE_HBM_BUDGET_MB"] = str(args.budget_mb)
    loader = _resolve_loader(args.loader)
    router = Router(loader=loader, max_batch=args.max_batch).start()
    server = ServingServer(router, port=args.port)
    _write_port_file(args.gang_dir, rank, server.port, generation)

    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    print(
        json.dumps(
            {
                "serving_worker": "up",
                "rank": rank,
                "generation": generation,
                "port": server.port,
                "pid": os.getpid(),
            }
        ),
        flush=True,
    )
    with Heartbeat(
        args.gang_dir, rank,
        interval=args.heartbeat_interval,
        generation=generation,
    ):
        admin_drained = False
        drain_deadline = None
        while not stop.wait(0.2):
            if not router.draining:
                continue
            if drain_deadline is None:
                # an /admin/drain began: bound the wait like the
                # SIGTERM path does — a wedged in-flight group must
                # not pin a half-dead worker in 'draining' forever
                drain_deadline = time.monotonic() + knobs.get_float(
                    "SPARKDL_SERVE_DRAIN_TIMEOUT_S"
                )
            if (
                router.wait_drained(timeout=0)
                or time.monotonic() >= drain_deadline
            ):
                # drained via POST /admin/drain (or timed out trying):
                # this worker is done — exit so the supervisor
                # (complete_on_exit0=False) replaces it with a fresh
                # one: the rolling-restart path. A short linger first
                # keeps the draining state observable (gateway health
                # polls, operator probes) before the exit turns into a
                # gang relaunch.
                admin_drained = True
                break
        if admin_drained:
            time.sleep(2.0)
        # -- graceful drain: stop admitting, finish accepted work ----------
        router.drain()
        drained = router.wait_drained(
            timeout=knobs.get_float("SPARKDL_SERVE_DRAIN_TIMEOUT_S")
        )
        server.stop(close_router=True)
    print(
        json.dumps(
            {
                "serving_worker": "drained" if drained else "drain_timeout",
                "rank": rank,
                "generation": generation,
            }
        ),
        flush=True,
    )
    # exit 0 either way: a drain timeout is logged above, and the
    # supervisor's KILL escalation is the backstop for a true wedge
    return 0


def _gateway_main(args) -> int:
    from sparkdl_tpu.serving.gateway import ServingGateway
    from sparkdl_tpu.serving.server import configured_port

    port = args.port if args.port is not None else (configured_port() or 8000)
    try:
        gw = ServingGateway(
            num_workers=args.workers,
            port=port,
            gang_dir=args.gang_dir,
            loader_spec=args.loader,
            budget_mb=args.budget_mb,
            max_batch=args.max_batch,
        ).start()
    except ValueError as e:  # more workers than chips
        print(f"gateway: {e}", file=sys.stderr)
        return 2
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    print(
        json.dumps(
            {
                "gateway": "up",
                "port": gw.port,
                "workers": gw.num_workers,
                "gang_dir": gw.gang_dir,
                "endpoints": [
                    "POST /v1/predict",
                    "/v1/workers",
                    "/v1/models",
                    "/healthz",
                    "/metrics",
                    "POST /admin/drain",
                ],
            }
        ),
        flush=True,
    )
    try:
        while not stop.wait(1.0):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        gw.stop()
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m sparkdl_tpu.serving",
        description="Online serving layer: HTTP front-end + registry info.",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_serve = sub.add_parser("serve", help="run the HTTP serving endpoint")
    p_serve.add_argument(
        "--port",
        type=int,
        default=None,
        help="bind port (default SPARKDL_SERVE_PORT or 8000; 0 = ephemeral)",
    )
    p_serve.add_argument(
        "--budget-mb",
        type=float,
        default=None,
        help="HBM residency budget (overrides SPARKDL_SERVE_HBM_BUDGET_MB)",
    )
    p_serve.add_argument(
        "--max-batch",
        type=int,
        default=None,
        help="full batch geometry (overrides SPARKDL_SERVE_MAX_BATCH)",
    )

    p_gw = sub.add_parser(
        "gateway",
        help="run the supervised serving gang behind one routing door",
    )
    p_gw.add_argument(
        "--workers", type=int, default=None,
        help="gang size (default SPARKDL_GATEWAY_WORKERS)",
    )
    p_gw.add_argument("--port", type=int, default=None)
    p_gw.add_argument(
        "--gang-dir", default=None,
        help="port files + heartbeats + worker logs (default: a temp dir)",
    )
    p_gw.add_argument(
        "--loader", default=None,
        help="pkg.mod:function loader override for every worker",
    )
    p_gw.add_argument("--budget-mb", type=float, default=None)
    p_gw.add_argument("--max-batch", type=int, default=None)

    p_w = sub.add_parser(
        "worker", help="one supervised serving worker (gateway-launched)"
    )
    p_w.add_argument("--rank", type=int, required=True)
    p_w.add_argument("--gang-dir", required=True)
    p_w.add_argument("--port", type=int, default=0)
    p_w.add_argument("--loader", default=None)
    p_w.add_argument("--budget-mb", type=float, default=None)
    p_w.add_argument("--max-batch", type=int, default=None)
    p_w.add_argument("--heartbeat-interval", type=float, default=1.0)

    sub.add_parser(
        "models", help="print the registry with memory estimates"
    )

    args = parser.parse_args(argv)

    if args.cmd == "models":
        from sparkdl_tpu.models import supported_models

        print(json.dumps(supported_models(with_memory=True), indent=2))
        return 0
    if args.cmd == "worker":
        return _worker_main(args)
    if args.cmd == "gateway":
        return _gateway_main(args)

    # serve
    from sparkdl_tpu.serving.router import Router
    from sparkdl_tpu.serving.server import ServingServer, configured_port

    if args.budget_mb is not None:
        os.environ["SPARKDL_SERVE_HBM_BUDGET_MB"] = str(args.budget_mb)
    _serving_env_defaults()
    port = args.port if args.port is not None else (configured_port() or 8000)
    router = Router(max_batch=args.max_batch).start()
    server = ServingServer(router, port=port)
    print(
        json.dumps(
            {
                "serving": "up",
                "port": server.port,
                "endpoints": [
                    "POST /v1/predict",
                    "/v1/models",
                    "/healthz",
                    "/metrics",
                ],
            }
        ),
        flush=True,
    )
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop(close_router=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
