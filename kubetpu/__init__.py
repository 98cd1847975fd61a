"""kubetpu — a TPU-native batch scheduling framework.

A from-scratch re-design of the Kubernetes scheduling stack (reference:
kube-scheduler, /root/reference/pkg/scheduler) for TPU hardware: the in-tree
Filter plugins become boolean-mask kernels and the Score plugins become
vectorized JAX/XLA kernels over a device-resident ``(pods, nodes)`` tensor;
the per-pod greedy ``scheduleOne`` loop becomes a single device-resident
loop (greedy-parity mode) or a capacity-coupled batched assignment
(Sinkhorn mode), sharded over a TPU mesh with ``shard_map``/``pjit``.

Subpackages
-----------
- ``api``:       typed cluster objects (Pod, Node, selectors, taints, affinity)
                 — the scheduling-relevant envelope of ``staging/src/k8s.io/api``.
- ``state``:     host snapshot store + string interning + device tensorization
                 — the analog of ``pkg/scheduler/backend/cache``.
- ``ops``:       filter/score kernels — the analog of
                 ``pkg/scheduler/framework/plugins``.
- ``assign``:    assignment engines (greedy scan, Sinkhorn bin-pack) — replaces
                 ``pkg/scheduler/schedule_one.go``'s argmax-per-pod.
- ``parallel``:  mesh construction + sharding rules (node/pod axis over ICI).
- ``framework``: plugin registry, profiles, KubeSchedulerConfiguration subset —
                 the analog of ``pkg/scheduler/framework/runtime``.
- ``sched``:     scheduling queue + batch scheduling/binding cycles.
- ``bridge``:    extender-webhook wire protocol server (the integration seam
                 with a real kube-scheduler, ``pkg/scheduler/extender.go``).
- ``perf``:      scheduler_perf-style workloads, driven in one process (tests).
- ``utils``:     metrics, feature gates, logging.

Integer-exact score parity with the reference requires 64-bit resource
arithmetic (quantities are int64 in the reference, and memory-bytes overflow
int32), so importing this package enables jax x64 mode. kubetpu is an
application framework — the process is expected to be a scheduler. If you are
embedding the host-side API types into a process whose JAX numerics must stay
32-bit, set ``KUBETPU_NO_X64=1`` before import and avoid the device kernels.
"""

import os

import jax

if not os.environ.get("KUBETPU_NO_X64"):
    jax.config.update("jax_enable_x64", True)

# ONE placement rule for JAX's persistent compilation cache, for every entry
# point (chip_smoke.py and ``python -m kubetpu ...`` both import this
# package first): whoever launches the process places the cache through
# JAX_COMPILATION_CACHE_DIR, which jax reads into its config at import, and
# then nothing is set here. Otherwise
# the cache lives at the FIXED path <checkout>/.jax_cache — a later process
# only finds the entries at the same path, so never a temporary or
# pid/time-derived one. Assigning the environment variable here would come
# too late (jax is already imported): the default goes through jax.config.
if jax.config.jax_compilation_cache_dir is None:
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    ))
# jax's default stores only programs that took >= 1 s to compile; a
# scheduler's start-up is the bucket ladder plus dozens of sub-second
# programs (scatters, gathers, probes), and a restart should pay for none
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_stamp() -> dict:
    """What this process's JAX holds, as JAX reports it — stamped on every
    perf record and on the scheduler's readiness banner so a run on a
    machine without a chip can never be read as one with. Initialises the
    backend: only a process that is meant to own the chip calls it."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
    }


__version__ = "0.4.0"
