"""Multi-process training runtime: jax.distributed meshes + the launcher.

One fit, many processes. ``parallel/mapreduce.py`` and
``parallel/update_sharding.py`` were built as THE SPMD seams; this module
drives them across the process boundary: a ``jax.distributed``-initialized
runtime where every process contributes its local devices to ONE global
mesh, and the existing ``map_shards``/``MapReduceProgram`` programs run
over it unchanged — the reference's "add TaskManagers, keep the job"
story, with SPMD lockstep replacing the coordinator RPC.

Three pieces:

- :func:`init_distributed` — env-mappable, idempotent cluster join. The
  same call works as code (explicit coordinator/num_processes/process_id),
  as environment (``FLINK_ML_TPU_COORDINATOR`` et al. — what the launcher
  sets), or as a no-op in a plain single-process run. Composes with
  ``mesh.init_distributed`` (the probe layer) rather than replacing it.
- :func:`build_mesh` — the global mesh. Multi-process runtimes get a
  ``(dcn, data)`` mesh with the process axis OUTERMOST (devices grouped
  by owning process), so the inter-process fabric is an explicit named
  axis: the hierarchical reduce (collective.py) and the hybrid-mesh
  programs address it, and ``data_axes(mesh)`` returns ``("dcn",
  "data")`` so every existing fit shards and reduces over both axes with
  zero algorithm changes. Single-process runtimes get the plain flat
  mesh — ``build_mesh`` is safe to call unconditionally.
- :func:`launch` — the CI launcher: N CPU processes, each with
  ``--xla_force_host_platform_device_count=L`` local devices (the PR 6
  simulation precedent, now one mesh ACROSS processes instead of inside
  one), a free localhost coordinator port, and the env mapping below.
  ``python -m flink_ml_tpu.parallel.distributed -n 2 -d 4 -- prog.py``
  runs ``prog.py`` in every process; per-process trace/metrics artifacts
  land in one shared trace dir and merge at read time (the hostpool
  ``spans-*.jsonl`` idiom extended with process labels —
  observability/exporters.py).

Env mapping (set by the launcher, readable by any entry point):

======================================  =====================================
``FLINK_ML_TPU_COORDINATOR``            coordinator ``host:port``
``FLINK_ML_TPU_NUM_PROCESSES``          total process count
``FLINK_ML_TPU_PROCESS_ID``             this process's index (0-based)
``FLINK_ML_TPU_LOCAL_DEVICES``          simulated local device count (CPU)
======================================  =====================================
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import List, Optional, Sequence

#: env mapping (docs/distributed.md "Multi-process meshes")
COORDINATOR_ENV = "FLINK_ML_TPU_COORDINATOR"
NUM_PROCESSES_ENV = "FLINK_ML_TPU_NUM_PROCESSES"
PROCESS_ID_ENV = "FLINK_ML_TPU_PROCESS_ID"
LOCAL_DEVICES_ENV = "FLINK_ML_TPU_LOCAL_DEVICES"

__all__ = [
    "COORDINATOR_ENV", "NUM_PROCESSES_ENV", "PROCESS_ID_ENV",
    "LOCAL_DEVICES_ENV", "init_distributed", "init_from_env",
    "process_count", "process_index", "process_label", "build_mesh",
    "launch", "main",
]


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "%s=%r is not an integer; ignoring it", name, raw)
        return None


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_devices: Optional[int] = None,
                     **kwargs) -> bool:
    """Join (or confirm) the multi-process JAX runtime. Idempotent: an
    already-joined runtime, a single-process configuration, and a repeat
    call are all safe no-ops. Returns True when the process is part of a
    live multi-process runtime afterwards.

    Arguments default to the env mapping above (what :func:`launch`
    sets), so entry points call ``init_distributed()`` unconditionally —
    exactly like ``mesh.init_distributed``, which this wraps: the probe,
    the already-initialized check, and the auto-detection fallback all
    live there; this layer adds the env mapping, the simulated
    local-device count and the CPU cross-process transport.

    ``local_devices`` (or ``FLINK_ML_TPU_LOCAL_DEVICES``) forces that
    many host-platform devices per process — only honored when jax has
    not initialized its backends yet (the launcher sets it in the child
    env, before the child imports jax, which is the supported order).
    """
    if coordinator is None:
        coordinator = os.environ.get(COORDINATOR_ENV) or None
    if num_processes is None:
        num_processes = _env_int(NUM_PROCESSES_ENV)
    if process_id is None:
        process_id = _env_int(PROCESS_ID_ENV)
    if local_devices is None:
        local_devices = _env_int(LOCAL_DEVICES_ENV)

    if local_devices and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{int(local_devices)}").strip()

    if coordinator is None and num_processes is None:
        # nothing configured: stay single-process without touching the
        # auto-detection path (mesh.init_distributed would probe cluster
        # metadata; unconfigured library users should not pay that)
        return False

    import jax

    if coordinator is not None and (num_processes or 1) > 1:
        # multi-process CPU needs a cross-process collective transport;
        # gloo ships with jaxlib and this must be set before backend init
        # (harmless + ignored on TPU runtimes, where ICI/DCN is native)
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:  # pragma: no cover — option absent on this line
            pass

    from flink_ml_tpu.observability import profiling
    from flink_ml_tpu.parallel import mesh as _mesh

    # the distributed-init rung of the boot ladder (ml.boot
    # phaseMs{phase="distributed-init"}, observability/profiling.py)
    with profiling.boot_phase("distributed-init"):
        return _mesh.init_distributed(coordinator_address=coordinator,
                                      num_processes=num_processes,
                                      process_id=process_id, **kwargs)


def init_from_env() -> bool:
    """:func:`init_distributed` with every argument from the env mapping
    — the one-liner for scripts launched by :func:`launch`."""
    return init_distributed()


def _jax_if_loaded():
    """The jax module when something already imported it, else None —
    artifact-labeling helpers must never be the thing that initializes a
    backend (exporters run in the trace CLI too)."""
    return sys.modules.get("jax")


def process_count() -> int:
    """Total processes in the runtime: the env mapping when the
    launcher set it (authoritative even before jax initializes — a
    child must label its artifacts correctly from the first span), else
    jax's count when jax is already loaded, else 1."""
    env = _env_int(NUM_PROCESSES_ENV)
    if env is not None:
        return env
    jax = _jax_if_loaded()
    if jax is not None:
        try:
            return int(jax.process_count())
        except Exception:
            pass
    return 1


def process_index() -> int:
    """This process's 0-based index (same sources as
    :func:`process_count`)."""
    env = _env_int(PROCESS_ID_ENV)
    if env is not None:
        return env
    jax = _jax_if_loaded()
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:
            pass
    return 0


def process_label() -> Optional[int]:
    """The index to label artifacts with, or None in a single-process
    runtime — the seam tracing/exporters use to name ``spans-p<k>-*``
    files and stamp ``process=`` onto records: two hosts can share a
    pid, so pid-only artifact names silently collide when a trace dir is
    shared across processes."""
    if process_count() > 1:
        return process_index()
    return None


def build_mesh(local_axis: Optional[int] = None):
    """The global mesh for this runtime.

    Multi-process: a ``(dcn, data)`` mesh — the process axis (named
    ``DCN_AXIS``: it IS the slow inter-host fabric) outermost with one
    row per process, devices grouped by their owning process in
    process-index order, the fast intra-process axis inside. Existing
    programs consume it through ``data_axes``/``data_pspec`` exactly
    like a hybrid multi-slice mesh, and the hierarchical reduce
    (collective.py) uses the axis split to keep the heavy legs local.

    Single-process: the plain flat data mesh (``create_mesh()``), so
    callers invoke this unconditionally.

    ``local_axis`` overrides the per-process device count (must divide
    evenly); default is every process's full local complement.
    """
    import numpy as np

    import jax

    from flink_ml_tpu.observability import profiling
    from flink_ml_tpu.parallel.mesh import (
        DATA_AXIS, DCN_AXIS, create_mesh)

    # the mesh-build rung of the boot ladder — on a cold runtime the
    # first jax.devices() call below pays backend/client init
    with profiling.boot_phase("mesh-build"):
        if jax.process_count() <= 1:
            return create_mesh()
        devices = sorted(
            jax.devices(),
            key=lambda d: (int(getattr(d, "process_index", 0)),
                           int(d.id)))
        n_proc = jax.process_count()
        per_proc = len(devices) // n_proc
        if local_axis is not None:
            if per_proc % int(local_axis):
                raise ValueError(
                    f"local_axis={local_axis} does not divide the "
                    f"{per_proc} devices each process contributes")
            per_proc = int(local_axis)
        arr = np.asarray(devices).reshape(n_proc, per_proc)
        from jax.sharding import Mesh

        return Mesh(arr, (DCN_AXIS, DATA_AXIS))


# -- the CI launcher ----------------------------------------------------------

def _free_port() -> int:
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(argv: Sequence[str], num_processes: int, local_devices: int = 1,
           env: Optional[dict] = None, timeout: float = 900.0,
           coordinator_port: Optional[int] = None,
           child_grace_s: float = 30.0) -> List[dict]:
    """Run ``argv`` as ``num_processes`` coordinated CPU processes.

    CPU-only CI harness: a chip belongs to one process at a time, so no
    entry point that needs the chip goes through here — four chips are
    ONE process and a four-device mesh.

    Each child gets the env mapping (coordinator on a free localhost
    port, its process id, the simulated local device count),
    ``JAX_PLATFORMS=cpu`` and the host-platform XLA flag — the child
    entry point just calls :func:`init_from_env` (or
    ``init_distributed()``) before building its mesh. Children run
    concurrently (they must: the distributed service blocks until every
    process joins); output is captured per process.

    Returns one record per process: ``{"process", "returncode",
    "exitOrder", "stdout", "stderr"}``, in process order —
    ``exitOrder`` is the poll-observed exit sequence (0 = first to
    exit, None when the launcher never saw it exit before draining),
    which lets an elastic driver name the FIRST signal death (the true
    victim) rather than a grace-killed survivor. Raises nothing on a child
    failure — the caller owns the verdict (the bench gates on it) — but
    a TimeoutExpired kills the whole group (a wedged coordinator must
    not hang CI forever).

    ``child_grace_s`` is the per-child liveness deadline: once ANY
    child exits nonzero, its surviving siblings get this many seconds
    to finish before the group is killed and the records (with the real
    failing rc) are returned. Without it a crashed child's exit code
    was held hostage by a wedged sibling until the FULL ``timeout`` —
    a lost worker wedges the whole lockstep group mid-collective, so
    that was the common case, not the corner. The killed survivors
    report their signal rc (e.g. ``-9``); the caller still owns the
    verdict."""
    port = coordinator_port or _free_port()
    base = dict(os.environ)
    base.update(env or {})
    base["JAX_PLATFORMS"] = "cpu"
    base[COORDINATOR_ENV] = f"127.0.0.1:{port}"
    base[NUM_PROCESSES_ENV] = str(int(num_processes))
    base[LOCAL_DEVICES_ENV] = str(int(local_devices))
    # causal stitching (docs/observability.md "Causal tracing"): every
    # child inherits ONE trace context through the env — the launcher's
    # current span when it has one, else a fresh trace-only context —
    # so each process's root spans join the SAME trace and the merged
    # spans-p<k>-*.jsonl artifacts stitch into one causal run instead
    # of N disconnected per-process traces. An explicitly provided
    # parent (env= or the surrounding environment) wins.
    from flink_ml_tpu.observability import tracing

    if not base.get(tracing.TRACE_PARENT_ENV):
        ctx = (tracing.tracer.current_context()
               or tracing.fresh_context())
        base[tracing.TRACE_PARENT_ENV] = ctx.to_header()
    flags = base.get("XLA_FLAGS", "")
    # strip any inherited device-count flag: the child's count must be
    # the launcher's, not the parent test env's
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    base["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count="
        f"{int(local_devices)}").strip()

    procs = []
    for pid in range(int(num_processes)):
        child_env = dict(base)
        child_env[PROCESS_ID_ENV] = str(pid)
        procs.append(subprocess.Popen(
            list(argv), env=child_env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))

    # drain EVERY child concurrently: the children run one collective
    # program in lockstep, so a single child blocked on a full stdout
    # pipe (communicate() drains sequentially) would stall the whole
    # group mid-psum until the timeout killed it
    collected = [None] * len(procs)

    def drain(i, proc):
        collected[i] = proc.communicate()

    threads = [threading.Thread(target=drain, args=(i, p), daemon=True)
               for i, p in enumerate(procs)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout
    grace_deadline = None  # armed by the first nonzero child exit
    exit_order = [None] * len(procs)  # poll-observed exit sequence
    exit_seq = 0
    while True:
        alive = [t for t in threads if t.is_alive()]
        if not alive:
            break
        now = time.monotonic()
        for i, p in enumerate(procs):
            if exit_order[i] is None and p.poll() is not None:
                exit_order[i] = exit_seq
                exit_seq += 1
        if now >= deadline:
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
            for t in threads:
                t.join(10.0)
            raise subprocess.TimeoutExpired(list(argv), timeout)
        if grace_deadline is None:
            if any(p.poll() is not None and p.returncode != 0
                   for p in procs):
                grace_deadline = now + max(float(child_grace_s), 0.0)
        elif now >= grace_deadline:
            # per-child liveness deadline tripped: a crashed child's rc
            # must not be held hostage by a wedged sibling until the
            # full group timeout — kill the survivors and report
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
            for t in threads:
                t.join(10.0)
            break
        alive[0].join(0.05)
    records = []
    for pid, (proc, got) in enumerate(zip(procs, collected)):
        out, err = got if got is not None else ("", "")
        records.append({"process": pid, "returncode": proc.returncode,
                        "exitOrder": exit_order[pid],
                        "stdout": out, "stderr": err})
    return records


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m flink_ml_tpu.parallel.distributed -n 2 -d 4 --
    script.py args...`` — exit 0 iff every process exited 0; each
    child's output is replayed prefixed with its process index."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="flink_ml_tpu.parallel.distributed",
        description="multi-process CPU launcher (docs/distributed.md)")
    parser.add_argument("-n", "--processes", type=int, default=2)
    parser.add_argument("-d", "--local-devices", type=int, default=1)
    parser.add_argument("--timeout", type=float, default=900.0)
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="program to run (prefix with -- to separate)")
    args = parser.parse_args(argv)
    command = list(args.command)
    if command and command[0] == "--":
        # only the FIRST "--" separates launcher args from the command;
        # later ones belong to the child program's own argv
        command = command[1:]
    if not command:
        parser.error("no command given")
    if command[0].endswith(".py"):
        command = [sys.executable] + command
    results = launch(command, args.processes, args.local_devices,
                     timeout=args.timeout)
    rc = 0
    for rec in results:
        for stream, text in (("out", rec["stdout"]),
                             ("err", rec["stderr"])):
            for line in (text or "").splitlines():
                print(f"[p{rec['process']}:{stream}] {line}",
                      file=sys.stderr if stream == "err" else sys.stdout)
        rc = rc or rec["returncode"]
    return rc


if __name__ == "__main__":
    sys.exit(main())
