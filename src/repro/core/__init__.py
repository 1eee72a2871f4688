# DASO — the paper's primary contribution: hierarchical, asynchronous,
# selective data-parallel optimization (Coquelin et al. 2021).
from repro.core.daso import (  # noqa: F401
    DasoConfig,
    Exchange,
    blocking_sync,
    daso_train_step,
    dereplicate_params,
    global_receive,
    global_send,
    local_step,
    replicate_params,
)
from repro.core.schedule import (DasoController,  # noqa: F401
                                 HierDasoController, Mode, join_mode,
                                 split_mode)
from repro.core.compression import compress_bf16_roundtrip  # noqa: F401
# Compiled macro-cycle executor + strategy registry (one XLA dispatch per
# controller cycle instead of one per step).
from repro.core.executor import (  # noqa: F401
    CyclePlan,
    MacroCycleExecutor,
    Strategy,
    get_strategy,
    list_strategies,
    make_strategy,
    register_strategy,
    run_compiled_training,
)
