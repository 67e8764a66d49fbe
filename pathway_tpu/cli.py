"""``pathway-tpu`` command line — multi-process launcher
(reference: python/pathway/cli.py:53-260 — ``pathway spawn`` /
``pathway replay`` / ``pathway spawn-from-env``).

The reference spawns N engine processes that form a timely cluster over
TCP (PATHWAY_PROCESS_ID / PATHWAY_PROCESSES / PATHWAY_FIRST_PORT).  The
TPU-native analog launches the same user program once per host process; each
process's ``pw.run()`` consumes the exported topology via
``pathway_tpu.parallel.distributed.maybe_initialize()`` — process 0 hosts
the jax coordination service at PATHWAY_COORDINATOR_ADDRESS and the
processes form ONE global device mesh (collectives over ICI/DCN, gloo on
CPU) instead of a socket cluster.  ``spawn`` starts every process on this
host, so with an accelerator present it refuses more than one (a chip
belongs to one process; one process drives all local chips).  See
parallel/distributed.py for the execution model and
tests/test_distributed.py for the 2-process parity tests.

``pathway-tpu run <template.yaml>`` serves a YAML template app on whatever
device JAX finds and prints that device at start-up.
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

from . import config

__all__ = ["main", "spawn_program"]


def _topology_env(
    process_id: int,
    processes: int,
    first_port: int,
    base: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    env = dict(os.environ if base is None else base)
    env["PATHWAY_PROCESS_ID"] = str(process_id)
    env["PATHWAY_PROCESSES"] = str(processes)
    env["PATHWAY_FIRST_PORT"] = str(first_port)
    # consumed by parallel/distributed.maybe_initialize() (called from
    # pw.run()): process 0 hosts the jax coordination service here
    env["PATHWAY_COORDINATOR_ADDRESS"] = f"127.0.0.1:{first_port}"
    return env


def _accelerator_backend(env: Dict[str, str]) -> Optional[str]:
    """The accelerator backend a child started with ``env`` would find, or
    None for the CPU.  Asked of a short-lived subprocess, because this
    launcher must not take the chip itself."""
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        env=env, capture_output=True, text=True, timeout=180,
    )
    lines = probe.stdout.strip().splitlines()
    backend = lines[-1].strip() if probe.returncode == 0 and lines else ""
    return backend if backend not in ("", "cpu") else None


def spawn_program(
    program: str,
    arguments: Sequence[str],
    *,
    processes: int = 1,
    first_port: int = 10000,
    env_extra: Optional[Dict[str, str]] = None,
    timeout: Optional[float] = None,
) -> int:
    """Launch ``processes`` copies of ``program``; returns the first
    non-zero exit code observed (the teardown cause), or 0 if all succeed.
    A failing process tears the others down (the reference's
    all-pods-must-be-present model, SURVEY §5.3).  ``timeout`` (seconds):
    kill anything still running then; returns 124 only when the timeout is
    the first failure (an earlier member's non-zero code wins).

    Every process starts on THIS host and claims every local chip, and a
    chip belongs to one process: on a host with an accelerator more than
    one process is refused (exit code 2) instead of left to fail at the
    chip and wait out a coordination barrier.  One process drives all the
    chips of a host; the multi-process cluster is for the CPU host plane
    (``JAX_PLATFORMS=cpu``)."""
    if processes > 1:
        backend = _accelerator_backend({**os.environ, **(env_extra or {})})
        if backend is not None:
            print(
                f"pathway-tpu spawn: refusing to start {processes} processes "
                f"on one {backend} host — each would claim every local chip, "
                "and a chip belongs to one process.  Run one process (it "
                "drives all local chips), or set JAX_PLATFORMS=cpu for a "
                "host-plane cluster.",
                file=sys.stderr,
            )
            return 2
    handles: List[subprocess.Popen] = []
    try:
        for pid in range(processes):
            env = _topology_env(pid, processes, first_port)
            if env_extra:
                env.update(env_extra)
            handles.append(
                subprocess.Popen([program, *arguments], env=env)
            )
        # wait on ANY process: a crashed member must tear the others down
        # immediately, even while lower-index members are still running
        import time as _time

        deadline = _time.time() + timeout if timeout else None
        exit_code = 0
        live = list(handles)
        terminated = False
        while live:
            progressed = False
            for h in list(live):
                rc = h.poll()
                if rc is None:
                    continue
                live.remove(h)
                progressed = True
                if rc != 0 and not terminated:
                    exit_code = rc
                    terminated = True
                    for other in live:
                        if other.poll() is None:
                            other.send_signal(signal.SIGTERM)
            if live and deadline is not None and _time.time() > deadline:
                for h in live:
                    if h.poll() is None:
                        h.kill()
                for h in live:
                    h.wait()
                # keep an already-observed failure code as the cause; 124
                # only when the timeout itself is the first failure
                return exit_code or 124
            if live and not progressed:
                _time.sleep(0.05)
        return exit_code
    except KeyboardInterrupt:
        for h in handles:
            if h.poll() is None:
                h.send_signal(signal.SIGINT)
        for h in handles:
            h.wait()
        return 130


def run_template(
    template: str,
    host: Optional[str] = None,
    port: Optional[int] = None,
) -> int:
    """Load a YAML template app (the L7 surface — reference template apps,
    docs/2.developers/7.templates/) and serve it: a ``question_answerer``
    gets the QA REST routes, a bare ``document_store`` the retrieval routes,
    and a plain pipeline just runs."""
    import jax

    from pathway_tpu.internals.yaml_loader import load_yaml

    with open(template) as f:
        cfg = load_yaml(f)
    if not isinstance(cfg, dict):
        raise SystemExit(f"template {template} must be a mapping, got {type(cfg)}")
    host = host or cfg.get("host", "127.0.0.1")
    port = port or int(cfg.get("port", 8000))
    # the app runs on whatever JAX found; say which, so a launcher (or
    # chip_smoke.py) can tell a chip from a CPU start
    dev = jax.devices()[0]
    print(
        f"jax {jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} devices={len(jax.devices())}",
        flush=True,
    )

    qa = cfg.get("question_answerer")
    if qa is not None:
        qa.build_server(host=host, port=port)
        print(f"serving QA endpoints at http://{host}:{port}", flush=True)
        qa.run_server(with_cache=False)
        return 0
    store = cfg.get("document_store")
    if store is not None:
        from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

        server = DocumentStoreServer(host, port, store)
        print(f"serving DocumentStore at http://{host}:{port}", flush=True)
        server.run(with_cache=False)
        return 0
    import pathway_tpu as pw

    pw.run()
    return 0


def _persistence_env(args) -> Dict[str, str]:
    env: Dict[str, str] = {}
    if getattr(args, "record", False) or getattr(args, "mode", None):
        path = getattr(args, "record_path", None) or "./record"
        env["PATHWAY_PERSISTENT_STORAGE"] = path
    if getattr(args, "mode", None):
        env["PATHWAY_PERSISTENCE_MODE"] = args.mode.upper()
    return env


def _add_spawn_args(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "-n",
        "--processes",
        type=int,
        default=1,
        help="number of host processes to launch",
    )
    p.add_argument(
        "--first-port",
        type=int,
        default=10000,
        help="port of the coordination service hosted by process 0",
    )
    p.add_argument("program")
    p.add_argument("arguments", nargs=argparse.REMAINDER)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="pathway-tpu", description=__doc__.splitlines()[0]
    )
    sub = parser.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("spawn", help="run a program on N coordinated processes")
    _add_spawn_args(sp)
    sp.add_argument(
        "--record", action="store_true", help="record input connector data"
    )
    sp.add_argument(
        "--record-path", default=None, help="snapshot storage location"
    )

    rp = sub.add_parser("replay", help="re-run a program from recorded data")
    _add_spawn_args(rp)
    rp.add_argument(
        "--record-path", default="./record", help="snapshot storage location"
    )
    rp.add_argument(
        "--mode",
        choices=["batch", "speedrun"],
        default="batch",
        help="replay timing: batch (collapse) or speedrun (original pacing)",
    )

    se = sub.add_parser(
        "spawn-from-env",
        help="spawn with arguments taken from $PATHWAY_SPAWN_ARGS",
    )
    se.add_argument("program", nargs="?", default=None)
    se.add_argument("arguments", nargs=argparse.REMAINDER)

    rn = sub.add_parser(
        "run", help="run a YAML template app (see templates/)"
    )
    rn.add_argument("template", help="path to a template YAML")
    rn.add_argument("--host", default=None, help="override the template host")
    rn.add_argument(
        "--port", type=int, default=None, help="override the template port"
    )

    args = parser.parse_args(argv)

    if args.command == "run":
        return run_template(args.template, host=args.host, port=args.port)

    if args.command == "spawn-from-env":
        spawn_args = shlex.split(config.get("cli.spawn_args"))
        extra = [args.program] if args.program else []
        return main(["spawn", *spawn_args, *extra, *args.arguments])

    env_extra = _persistence_env(args)
    return spawn_program(
        args.program,
        args.arguments,
        processes=args.processes,
        first_port=args.first_port,
        env_extra=env_extra,
    )


if __name__ == "__main__":
    sys.exit(main())
