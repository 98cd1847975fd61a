"""kubetpu.launch — the multi-process control plane (PR 13).

Everything above the kernel used to be measured inside one Python process;
this package is the subsystem that runs the control plane as REAL OS
processes instead: a readiness-banner contract (``banner``), a process
supervisor owning the full child lifecycle (``supervisor`` — THE
``subprocess.Popen`` seam, pinned by graftcheck PS001), and the standard
topology builder (``cluster`` — apiserver + N scheduler replicas +
optional collector + watch-fanout drivers, each driver carrying a
``fanout.WatchFanout``), shared verbatim by the tier-1 multi-process smoke
and ``kubetpu up``.
"""

from .banner import (  # noqa: F401
    READY_PREFIX,
    emit_banner,
    format_banner,
    parse_banner,
)
from .supervisor import (  # noqa: F401
    Child,
    ChildSpec,
    RestartPolicy,
    Supervisor,
    SupervisorError,
)
from .cluster import (  # noqa: F401
    Cluster,
    apiserver_spec,
    collector_spec,
    kubetpu_argv,
    scheduler_spec,
    watch_driver_spec,
)
from .fanout import WatchFanout  # noqa: F401
