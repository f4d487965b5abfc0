from .version import __version__  # noqa: F401

# The public surface mirrors the reference's __init__ (Snapshot, Stateful,
# StateDict, RNGState, __version__) plus this package's manager layer.
from . import faultinject  # noqa: F401
from . import telemetry  # noqa: F401
from .manifest import CorruptSnapshotError, SnapshotMetadata  # noqa: F401
from .stateful import AppState, Stateful  # noqa: F401
from .state_dict import StateDict  # noqa: F401
from .rng_state import RNGState  # noqa: F401
from .snapshot import (  # noqa: F401
    PendingRestore,
    PendingSnapshot,
    Snapshot,
    StaleCommitError,
)
from .manager import CheckpointManager  # noqa: F401
from .preemption import PreemptionWatcher, simulate_preemption_now  # noqa: F401
from .io_preparers.array import warmup_staging  # noqa: F401
from .dist_store import StoreConnectionLostError  # noqa: F401
