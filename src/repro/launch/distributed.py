"""Multi-process (multi-host) readiness: init wiring + rank-0 IO gating.

Two things live here, deliberately small:

* :func:`initialize` — the ``jax.distributed.initialize`` entry point with
  env-var fallbacks, so the same binary runs single-process (no-op) and
  under a multi-process launcher (``scripts/run_multihost.sh``, SLURM,
  GKE). After it returns, ``jax.devices()`` is the GLOBAL device list and
  ``jax.local_devices()`` this process's slice.
* :func:`is_main` / :func:`main_print` / :func:`main_only` — the
  ``process_index == 0`` gate every logging/IO site in the repo routes
  through (benchmark emit/dump, service log + snapshot writes, launch
  drivers, the telemetry layer's JSONL event-log writes in
  ``repro.obs.export``), so a multi-process run produces ONE copy of
  every artifact instead of ``process_count`` clobbering copies.
  Uninitialized (single-process) jax reports ``process_index() == 0``,
  so the gate is a no-op in every existing entry point. In-memory
  telemetry (``repro.obs`` counters/histograms) is deliberately NOT
  gated — every rank keeps its own registry; only exported artifacts
  are rank-0.

What multi-process does NOT change: the numeric contract. The composed
2D mesh (``fl/sharding.py::make_mesh2d``) is built from ``jax.devices()``
— the global list — so a 2-process x 4-device run builds the same
``(Dc, Dp)`` mesh as a 1-process x 8-device run and the per-device
programs are identical; only the device->process placement differs.

CPU only: the multi-process smoke (:func:`main`, driven by
``scripts/run_multihost.sh`` and tests/test_multihost.py) forces virtual
CPU device counts (``local_device_count``) and starts several processes.
Never run it on a chip: a chip belongs to one process, and one process
drives all the chips of a host (``chip_smoke.py --four-chips``). jax's CPU
backend implements the distributed *runtime* (coordinator, topology
exchange, global device enumeration) but NOT cross-process collectives
("Multiprocess computations aren't implemented on the CPU backend"), so
the smoke asserts topology and runs process-LOCAL compute only.
"""

from __future__ import annotations

import argparse
import functools
import os

import jax

_INITIALIZED = False


def is_main() -> bool:
    """True on the rank-0 process (and always in single-process runs)."""
    return jax.process_index() == 0


def main_print(*args, **kwargs) -> None:
    """``print`` on the rank-0 process only.

    The single shared logging gate: benchmarks' emit, the launch drivers'
    progress lines, and the service's replay banners all route here so a
    multi-process run logs once.
    """
    if is_main():
        print(*args, **kwargs)


def main_only(fn):
    """Run ``fn`` on rank 0 only; other processes get ``None``.

    For IO side effects (snapshot/log writes, JSON dumps) that must
    happen exactly once per *job*, not once per process. Not for values
    other ranks need — there is no broadcast here by design (the CPU
    backend has no cross-process collectives to broadcast with).
    """

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if is_main():
            return fn(*args, **kwargs)
        return None

    return wrapper


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               local_device_count: int | None = None) -> bool:
    """Wire up ``jax.distributed.initialize`` from args or environment.

    Args fall back to ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES``
    / ``JAX_PROCESS_ID``; with no coordinator configured anywhere this is
    a single-process no-op returning False (the common local path — every
    existing entry point keeps working untouched). Idempotent: a second
    call returns True without re-initializing.

    ``local_device_count`` pins this process's CPU device count (the
    multi-host CPU smoke gives each process 2 virtual devices); on real
    accelerators leave it None and the backend enumerates hardware.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return True
    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if coordinator_address is None:
        return False
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("JAX_PROCESS_ID", "0"))
    if local_device_count is not None:
        # Must land before the backend is instantiated; initialize() is
        # called before any jax.devices() in the entry points below.
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={local_device_count}"
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED = True
    return True


def main(argv=None) -> int:
    """Multi-process CPU smoke: init, assert topology, process-local compute.

    CPU only — it forces virtual CPU devices; never run it on a chip.
    Run one copy per process (scripts/run_multihost.sh drives 2 on
    localhost CPU). Asserts the distributed runtime agrees with the
    launcher's topology flags, runs a jitted reduction on LOCAL devices
    (no cross-process collectives — see module docstring), and rank 0
    prints the single OK line the CI leg greps for.
    """
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--coordinator", required=True,
                    help="host:port of the rank-0 coordinator")
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--local-devices", type=int, default=2,
                    help="virtual CPU devices per process")
    args = ap.parse_args(argv)

    initialize(coordinator_address=args.coordinator,
               num_processes=args.num_processes,
               process_id=args.process_id,
               local_device_count=args.local_devices)

    assert jax.process_count() == args.num_processes, \
        (jax.process_count(), args.num_processes)
    assert jax.process_index() == args.process_id, \
        (jax.process_index(), args.process_id)
    n_local = len(jax.local_devices())
    n_global = len(jax.devices())
    assert n_local == args.local_devices, (n_local, args.local_devices)
    assert n_global == args.num_processes * args.local_devices, \
        (n_global, args.num_processes, args.local_devices)
    # Every process sees every other process's devices in the global list.
    owners = sorted({d.process_index for d in jax.devices()})
    assert owners == list(range(args.num_processes)), owners

    # Process-local compute sanity (the CPU backend stops at cross-process
    # collectives, not at local jit).
    import jax.numpy as jnp
    total = jax.jit(lambda x: jnp.sum(x * x))(jnp.arange(64.0))
    assert float(total) == 85344.0, float(total)

    print(f"[process {jax.process_index()}/{jax.process_count()}] "
          f"local={n_local} global={n_global} ok", flush=True)
    main_print("MULTIHOST SMOKE OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
