"""Decentralized local-SGD training loop.

Reference parity: ConsensusML's training layer (SURVEY.md L4) — each worker
runs H local optimizer steps ("inner loop"), then a model-averaging outer
step over the gossip topology (BASELINE.json: "local-SGD inner loop and
model-averaging outer step", configs[2] "32-worker local-SGD (H=8)").

TPU-first design (north_star): the ENTIRE round — H forward/backward +
optimizer steps via ``lax.scan``, then the gossip collective — is ONE
``jax.jit``-compiled program under ``shard_map``, so XLA overlaps the
mixing collectives with compute and there is no host round-trip between
inner steps (the reference crosses the host boundary at every NCCL call).
"""

from consensusml_tpu.train.local_sgd import (  # noqa: F401
    LocalSGDConfig,
    LossAux,
    TrainState,
    batch_placement,
    make_collective_train_step,
    make_simulated_train_step,
    init_state,
    init_stacked_state,
)
from consensusml_tpu.train.schedules import (  # noqa: F401
    build_optimizer,
    lr_schedule,
)
from consensusml_tpu.train.outer import (  # noqa: F401
    SlowMoConfig,
    slowmo_init,
    slowmo_update,
)
from consensusml_tpu.train.evaluate import (  # noqa: F401
    causal_lm_eval_fn,
    classification_eval_fn,
    evaluate,
    make_stacked_eval_step,
    mlm_eval_fn,
)
