"""Test environment: force a pure-CPU JAX with an 8-device virtual mesh.

Tests are CPU-only by contract — they check answers, never speed, and
must pass on a machine with no accelerator. The platform is pinned through
``jax.config`` as well as the environment: ``jax.config.update`` still works
after ``import jax`` as long as no backend has been initialised, which is
the case when conftest runs, and the environment variable covers the
subprocesses tests spawn.

``xla_force_host_platform_device_count=8``: shardings are validated on a
virtual 8-device CPU mesh (same scheme as the driver's dryrun); the same
checks on real chips are ``chip_smoke.py``'s four-chip stage.

Concurrency hygiene (the graftcheck runtime half):

- ``faulthandler`` is enabled so a hard wedge dumps every thread's stack
  on SIGABRT/timeout instead of dying silently.
- ``threading.excepthook`` is captured: a worker thread dying with an
  uncaught exception (informer pump, dispatcher worker) FAILS the test
  that owned it, instead of the test hanging or passing vacuously while
  the thread's work never happened.
- The lock-order witness (``kubetpu.analysis.witness``) is installed for
  the concurrency-heavy test modules: every lock created by kubetpu code
  during those tests joins a global lock-order graph, and any cycle —
  a potential ABBA deadlock, even one whose losing interleaving never
  fired in this run — raises ``LockOrderError`` on the spot.
"""

import faulthandler
import os
import threading

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses we spawn
# hermetic: tests neither read nor fill the persistent compilation cache
# that ``import kubetpu`` places (a test that wants it re-enables it in
# its own subprocess)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

faulthandler.enable()

# ---------------------------------------------------------------------------
# worker-thread death → owning-test failure
# ---------------------------------------------------------------------------
_thread_errors: list = []
_orig_threading_hook = threading.excepthook


def _capture_thread_exception(args) -> None:
    # SystemExit in a thread is the documented clean-exit idiom — not a
    # death worth failing a test over
    if args.exc_type is not SystemExit:
        _thread_errors.append(
            f"thread {getattr(args.thread, 'name', '?')!r} died: "
            f"{args.exc_type.__name__}: {args.exc_value}"
        )
    _orig_threading_hook(args)


threading.excepthook = _capture_thread_exception


@pytest.fixture(autouse=True)
def _fail_on_thread_death():
    """A worker thread raising after this test started fails THIS test.
    Best-effort attribution: threads outlive joins rarely enough here
    that charging the current test is the honest default."""
    mark = len(_thread_errors)
    yield
    fresh = _thread_errors[mark:]
    if fresh:
        del _thread_errors[mark:]
        pytest.fail(
            "worker thread died during this test:\n  "
            + "\n  ".join(fresh),
            pytrace=False,
        )


# ---------------------------------------------------------------------------
# /metrics scrape lint: histogram + label-shape consistency
# ---------------------------------------------------------------------------

def assert_metrics_consistent(text: str) -> None:
    """Validate one Prometheus exposition page the way a scrape consumer
    would: per histogram child the bucket counts are cumulative
    (monotonically non-decreasing in ``le``), the ``+Inf`` bucket equals
    ``_count``, ``_sum`` is present (and non-negative when every bucket
    bound is), and within a family every sample carries the same label-name
    set (arity vs declaration). Every observability/apiserver test that
    scrapes /metrics runs its page through this (the ``metrics_lint``
    fixture), so a torn histogram or label drift fails the suite instead
    of a dashboard."""
    import math

    from kubetpu.metrics.textparse import parse_prometheus_text

    pm = parse_prometheus_text(text)
    for name, fam in pm.families.items():
        # label arity: one name set per sample name within the family
        # (histogram suffixes differ legitimately: _bucket adds "le")
        arity: dict[str, set] = {}
        for s in fam.samples:
            keys = frozenset(k for k, _ in s.labels)
            arity.setdefault(s.name, set()).add(keys)
        for sample_name, shapes in arity.items():
            assert len(shapes) == 1, (
                f"{sample_name}: inconsistent label sets {shapes}"
            )
        if fam.kind != "histogram":
            continue
        # group _bucket/_sum/_count by their non-le label set (the child)
        children: dict[tuple, dict] = {}
        for s in fam.samples:
            key = tuple(sorted((k, v) for k, v in s.labels if k != "le"))
            child = children.setdefault(
                key, {"buckets": [], "sum": None, "count": None}
            )
            if s.name == name + "_bucket":
                le = dict(s.labels).get("le")
                assert le is not None, f"{name}_bucket without le ({key})"
                bound = math.inf if le == "+Inf" else float(le)
                child["buckets"].append((bound, s.value))
            elif s.name == name + "_sum":
                child["sum"] = s.value
            elif s.name == name + "_count":
                child["count"] = s.value
        for key, child in children.items():
            assert child["buckets"], f"{name}{dict(key)}: no buckets"
            assert child["sum"] is not None, f"{name}{dict(key)}: no _sum"
            assert child["count"] is not None, f"{name}{dict(key)}: no _count"
            ordered = sorted(child["buckets"])
            counts = [c for _, c in ordered]
            assert counts == sorted(counts), (
                f"{name}{dict(key)}: bucket counts not cumulative: {ordered}"
            )
            assert ordered[-1][0] == math.inf, (
                f"{name}{dict(key)}: missing +Inf bucket"
            )
            assert ordered[-1][1] == child["count"], (
                f"{name}{dict(key)}: +Inf bucket {ordered[-1][1]} != "
                f"_count {child['count']}"
            )
            if ordered[-1][1] > 0 and ordered[0][0] >= 0:
                assert child["sum"] >= 0, (
                    f"{name}{dict(key)}: negative _sum with non-negative "
                    f"bounds"
                )


@pytest.fixture
def metrics_lint():
    """The /metrics consistency validator as a fixture — scrape-heavy
    tests run every exposition page they fetch through it."""
    return assert_metrics_consistent


# ---------------------------------------------------------------------------
# lock-order witness for the concurrency-heavy suites
# ---------------------------------------------------------------------------
#: modules whose tests create MemStore/informer/dispatcher/reflector locks
#: in-test — the witness watches their global acquisition order
_WITNESSED_MODULES = {
    "test_api_batching",      # dispatcher micro-batch + 4-worker stats
    "test_client_store",      # reflector/informer pump
    "test_apiserver",         # memstore under the threaded HTTP server
    "test_queue",             # scheduling queue churn
    "test_static_analysis",   # the witness's own tests
}


@pytest.fixture(autouse=True)
def _lock_order_witness(request):
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _WITNESSED_MODULES:
        yield None
        return
    from kubetpu.analysis import witness

    with witness.installed() as state:
        yield state
    if state.violations:
        pytest.fail(
            "lock-order witness found potential deadlock(s):\n  "
            + "\n  ".join(state.violations),
            pytrace=False,
        )
