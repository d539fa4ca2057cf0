"""Runtime utilities: checkpointing, metrics logging, tracing."""

from consensusml_tpu.utils.checkpoint import (  # noqa: F401
    AsyncSaver,
    checkpoint_round,
    checkpoint_world_size,
    replicated_scalar,
    restore_state,
    save_state,
)
from consensusml_tpu.utils.elastic import resize_state  # noqa: F401
from consensusml_tpu.utils.tree import (  # noqa: F401
    consensus_mean,
    masked_worker_mean,
)
from consensusml_tpu.utils.logging import MetricsLogger  # noqa: F401
from consensusml_tpu.utils.watchdog import ProgressWatchdog  # noqa: F401
from consensusml_tpu.utils.profiling import (  # noqa: F401
    RoundStats,
    RoundTimer,
    fence,
    trace,
)
