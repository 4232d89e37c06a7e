"""Multi-host initialization — the launcher-side counterpart of the
reference's ``hvd.init()`` over OpenMPI (/root/reference/train.py:412,
README.md:89-104).

On TPU pods there is no mpirun: every host runs the SAME program,
``jax.distributed.initialize()`` wires the hosts together over DCN (reading
the TPU metadata or the coordinator address from the environment), and
``jax.devices()`` then spans the whole pod. The data mesh covers all chips;
collectives ride ICI within a host/slice and DCN across — exactly where the
reference's "intra-machine dense, inter-machine sparse" simulation
(README.md:133-134) becomes a real two-tier fabric.

Launchers in ``script/`` show the three standard entries: single host,
``gcloud ... tpu-vm ssh --worker=all`` pods, and Slurm
(``sample_slurm.sh`` parity).
"""

import os
import time
from typing import Optional

import jax

__all__ = ["initialize_multihost", "is_coordinator", "local_batch_slice"]

#: the env triple the launcher scripts export — set all three or none
_ENV_TRIPLE = ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
               "JAX_PROCESS_ID")


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         init_retries: int = 3,
                         init_backoff: float = 1.0,
                         **timeouts) -> bool:
    """Call ``jax.distributed.initialize`` when running multi-host.

    With no arguments, TPU pod environments are auto-detected (the TPU
    metadata service supplies coordinator/worker ids). For CPU/GPU clusters
    (e.g. under Slurm) pass the coordinator explicitly or export
    ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``
    — the same triple the launcher scripts derive from Slurm variables
    (reference sample_slurm.sh:36-52 builds the equivalent -H list).

    ``timeouts`` forwards ``initialization_timeout`` /
    ``heartbeat_timeout_seconds`` / ``shutdown_timeout_seconds`` to
    ``jax.distributed.initialize``. The shutdown timeout matters on cold
    machines: processes reach the coordination service's shutdown barrier
    skewed by however much their compile times diverge, and the 300 s
    default is shorter than a cold multi-minute XLA compile — the barrier
    then kills the healthy process with DEADLINE_EXCEEDED.

    ``init_retries`` bounds retry of a failed
    ``jax.distributed.initialize`` (coordinator not up yet — the common
    race when workers of a pod/Slurm job start skewed), with exponential
    backoff starting at ``init_backoff`` seconds. The last attempt's
    error propagates.

    Returns True when distributed init ran, False for single-process runs.

    **Fail-fast on a partial env triple**: exporting only some of
    ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID`` is always a launcher bug — half-configured, a run
    would either hang waiting for processes that never dial in or
    silently come up single-process. Raise immediately with the missing
    names instead.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    # Slurm: per-task variables are only visible inside the srun task, so
    # read them here rather than exporting from the sbatch batch step
    # (where SLURM_PROCID is always 0)
    if num_processes is None and "SLURM_NTASKS" in os.environ:
        num_processes = int(os.environ["SLURM_NTASKS"])
    if process_id is None and "SLURM_PROCID" in os.environ:
        process_id = int(os.environ["SLURM_PROCID"])

    # fail-fast on a half-wired coordinator setup: once ANY of the triple
    # is supplied (args, env, or Slurm) the other two must resolve too —
    # a partial triple either hangs the job waiting for workers that
    # never dial in, or (num/id without a coordinator) silently comes up
    # single-process and trains on a fraction of the data
    resolved = {"JAX_COORDINATOR_ADDRESS": coordinator_address,
                "JAX_NUM_PROCESSES": num_processes,
                "JAX_PROCESS_ID": process_id}
    missing = [k for k, v in resolved.items() if v is None]
    if missing and len(missing) < len(resolved):
        raise RuntimeError(
            "partial multihost configuration: "
            f"{sorted(set(resolved) - set(missing))} resolved but "
            f"{missing} missing — export the full JAX_COORDINATOR_ADDRESS/"
            "JAX_NUM_PROCESSES/JAX_PROCESS_ID triple (or none of it for "
            "TPU-pod autodetection)")

    # TPU_WORKER_HOSTNAMES lists every host of a pod slice; a single entry
    # (no comma) is a one-host environment — nothing to wire up
    pod_hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    multi = (coordinator_address is not None
             or "," in pod_hosts
             or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))
    if not multi:
        return False
    # bounded retry around the coordination-service dial-in: worker
    # processes of a pod/Slurm job start skewed, and a worker that dials
    # in before the coordinator is listening gets a connection error it
    # should wait out, not die from. The fault-injection hook
    # (DGC_FAULTS="init_fail@N") exercises exactly this path in tests.
    from dgc_tpu.resilience import faults as _faults
    last_err = None
    for attempt in range(max(1, int(init_retries))):
        try:
            if _faults.should_fail_init(attempt):
                raise RuntimeError(
                    f"injected init failure (attempt {attempt})")
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **timeouts)
            return True
        except Exception as e:
            last_err = e
            if attempt + 1 >= max(1, int(init_retries)):
                raise
            delay = init_backoff * (2 ** attempt)
            print(f"[multihost] initialize attempt {attempt + 1} failed "
                  f"({type(e).__name__}: {e}); retrying in {delay:.1f}s")
            time.sleep(delay)
    raise last_err  # unreachable; keeps the control flow explicit


def is_coordinator() -> bool:
    """Rank-0 check (the reference's ``hvd.rank() == 0`` gating for logging
    and checkpoint bookkeeping, train.py:406-408)."""
    return jax.process_index() == 0


def local_batch_slice(global_batch: int, num_processes: int = None,
                      process_id: int = None):
    """The slice of a [global_batch, ...] host array this process should
    feed. Data loading is per-host: each process materializes only its
    shard (the DistributedSampler role, reference train.py:99-100).

    Fails fast on a non-divisible batch: flooring it here would make
    every host silently feed fewer samples — the effective global batch
    (and with it the LR scaling story) shrinks with no error anywhere
    downstream, since each host only ever sees its own shard.
    ``num_processes``/``process_id`` default to the live ``jax``
    values; tests pass them explicitly."""
    n = jax.process_count() if num_processes is None else int(num_processes)
    i = jax.process_index() if process_id is None else int(process_id)
    per, rem = divmod(int(global_batch), n)
    if rem:
        raise ValueError(
            f"global batch {global_batch} does not split evenly over {n} "
            f"processes (remainder {rem}): each host would silently feed "
            f"{per} samples and the effective global batch would shrink "
            f"to {per * n}. Use a global batch divisible by {n} (e.g. "
            f"{per * n} or {(per + 1) * n}) — adjust train.batch_size or "
            "train.num_batches_per_step")
    return slice(i * per, (i + 1) * per)


def host_local_to_global(arr, mesh, axis=None):
    """Host batch array -> global ``jax.Array`` sharded on the data axis.

    ``axis`` defaults to ALL the mesh's axis names — on the 1-D data mesh
    that is ``('data',)``, on the two-tier ``('hosts', 'local')`` mesh the
    batch shards over both tiers (process h's devices hold the h-th
    contiguous block, matching :func:`local_batch_slice`).

    Single process: a sharded device_put. Multi-process: a jit over a
    pod-spanning mesh cannot take process-local arrays — each host keeps
    only its :func:`local_batch_slice` and the global array is assembled
    with ``jax.make_array_from_process_local_data`` (the input-pipeline
    contract of multi-host JAX; this is the harness's replacement for the
    reference's DistributedSampler, train.py:99-100)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if axis is None:
        axis = tuple(mesh.axis_names)
    sharding = NamedSharding(mesh, P(axis))
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    local = arr[local_batch_slice(arr.shape[0])]
    return jax.make_array_from_process_local_data(sharding, local,
                                                  arr.shape)
