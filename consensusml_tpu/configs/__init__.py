"""The five reference workload configs, runnable at two scales.

Reference parity: BASELINE.json ``configs`` — each entry below reproduces
one of them (SURVEY.md L6 config system; mount empty, so hyperparameters
are standard-literature defaults, flagged as approximations):

  mnist_mlp      — "2-layer MLP on MNIST, 4 simulated workers, dense gossip"
  cifar_resnet50 — "ResNet-50 on CIFAR-10, 8-worker ring consensus all-reduce"
  bert_mlm       — "BERT-base MLM, 32-worker local-SGD (H=8) + periodic averaging"
  llama_lora     — "Llama-2-7B LoRA fine-tune, torus gossip over 4x4 mesh"
  gpt2_topk      — "GPT-2-medium pretrain, top-k sparsified + 8-bit quantized gossip"

and three that the reference does not have:

  nemotron_h_ep16 — a hybrid state-space / expert decoder (Nemotron 3 Nano's
                    block) under local SGD with exact gossip: one chip's share
                    of a 16-way expert-parallel deployment
  qwen3_next_ep16 — a linear-attention / many-small-experts decoder
                    (Qwen3-Next's layer: Gated DeltaNet, gated attention with
                    partial rotary, 512 SwiGLU experts of which 32 are held),
                    the same share of the same kind of deployment
  xing4_ep8       — a latent-attention / expert decoder on a residual of four
                    hyper-connected streams with a multi-token-prediction
                    module (Xing4.0-29B-A4B's layer: 192-wide keys beside
                    128-wide values, 64 SwiGLU experts of which 8 are held):
                    one chip's share of an 8-way expert-parallel deployment

``scale="smoke"`` shrinks model/worker count for CPU runs and CI;
``scale="full"`` is the reference-sized workload for TPU pods. Data is
procedurally generated by default (no network in this environment — see
consensusml_tpu.data); pass ``data_dir`` (CLI: ``--data-dir``) to train on
real files instead — MNIST idx / CIFAR-10 binary / memmapped token bins,
see :mod:`consensusml_tpu.data.files` for the expected layouts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import optax

from consensusml_tpu.compress import topk_int8_compressor
from consensusml_tpu.consensus import GossipConfig
from consensusml_tpu.data import (
    SyntheticClassification,
    SyntheticLM,
    lm_round_batches,
    round_batches,
)
from consensusml_tpu.topology import topology_from_name
from consensusml_tpu.train import (
    LocalSGDConfig,
    causal_lm_eval_fn,
    classification_eval_fn,
    mlm_eval_fn,
)
from consensusml_tpu.train.schedules import build_optimizer

__all__ = ["RunBundle", "CONFIGS", "build", "names"]


@dataclasses.dataclass
class RunBundle:
    """Everything the CLI needs to run one workload."""

    name: str
    world_size: int
    cfg: LocalSGDConfig
    loss_fn: Callable
    init_params: Callable[[jax.Array], Any]
    batches: Callable[..., Iterator[dict]]  # (rounds, seed, start=0) -> iterator
    description: str
    # held-out evaluation (train.evaluate): sums-returning metric fn +
    # UNSTACKED held-out batch iterator (n_batches, seed) -> iterator
    eval_fn: Callable | None = None
    eval_batches: Callable[[int, int], Iterator[dict]] | None = None
    # hybrid model parallelism (collective backend only): each worker
    # becomes a submesh of prod(sizes) devices; params are sharded over it
    # per tp_rules(axis) while gossip stays manual over the worker axes
    model_axes: tuple[tuple[str, int], ...] = ()
    tp_rules: Callable[[str], Any] | None = None
    # throughput data path: same (rounds, seed, start=0) contract, batches
    # assembled by the native C++ prefetch ring (train.py --native-loader);
    # None when the data source has no native kind. Image sources
    # additionally carry the u8-wire capability contract as attributes on
    # the closure — supports_wire=True plus the qscale/qoff dequant
    # constants, set ONLY by _native_cls_batches (train.py --native-wire
    # gates on supports_wire; keep all three together when adding a
    # builder)
    native_batches: Callable[..., Iterator[dict]] | None = None
    # optimizer rebuild hook for the CLI LR flags (--lr/--lr-schedule/
    # --warmup-rounds/--grad-clip): factory(lr_or_schedule) must rebuild
    # exactly cfg.optimizer; base_lr is the value the config baked in, and
    # base_warmup_steps the linear warm-up it baked in (optimizer steps)
    optimizer_factory: Callable | None = None
    base_lr: float | None = None
    base_warmup_steps: int = 0
    # the workload's flax module (architecture introspection: the HBM
    # accounting model reads layer/width/stage structure off it)
    model: Any = None


# keeps held-out LM sample streams disjoint from every training round key
_EVAL_SEED_OFFSET = 999_983


def _lm_eval_batches(data, batch: int, *, mlm_rate: float = 0.0):
    """Held-out LM batches: the dataset's holdout split when it has one
    (file-token val region), else the same stream under disjoint rng keys
    (procedural data — sample streams are keyed, so eval keys never appear
    in training)."""
    from consensusml_tpu.data.synthetic import mlm_corrupt
    import numpy as np

    def gen(n_batches: int, seed: int):
        held = data.holdout() if hasattr(data, "holdout") else data
        for r in range(n_batches):
            rng = np.random.default_rng((seed + _EVAL_SEED_OFFSET, r))
            ids = held.sample(rng, (batch,))
            if mlm_rate > 0:
                yield mlm_corrupt(ids, held, seed + _EVAL_SEED_OFFSET, r, mlm_rate)
            else:
                yield {"input_ids": jnp.asarray(ids)}

    return gen


def _file_tokens(data_dir: str | None, seq: int, vocab: int):
    """Token-file dataset from ``data_dir`` when present, else None.

    Validates (on a capped prefix — full memmap scans of multi-GB corpora
    are not free) that ids fit the config's vocab with the last slot
    reserved for [MASK]."""
    if data_dir is None:
        return None
    from consensusml_tpu.data.files import load_tokens
    import numpy as np

    data = load_tokens(data_dir, seq_len=seq, vocab_size=vocab)
    if data is None:
        return None
    probe = np.asarray(data.tokens[: 1_000_000])
    if probe.size and int(probe.max()) >= vocab - 1:
        raise ValueError(
            f"{data.source}: token id {int(probe.max())} >= vocab-1="
            f"{vocab - 1} (the last vocab slot is reserved as [MASK]); "
            "retokenize or pick a config with a larger vocab"
        )
    return data


def _lm_batches(data, world: int, h: int, batch: int, *, mlm_rate: float = 0.0):
    """Round-batch closure for either LM data source (procedural Markov
    stream or file-token windows)."""
    from consensusml_tpu.data.files import TokenFileDataset, token_round_batches

    if isinstance(data, TokenFileDataset):
        return lambda rounds, seed, start=0: token_round_batches(
            data, world, h, batch, rounds, seed, mlm_rate=mlm_rate, start=start
        )
    return lambda rounds, seed, start=0: lm_round_batches(
        data, world, h, batch, rounds, seed, mlm_rate=mlm_rate, start=start
    )


def _native_cls_batches(data, world: int, h: int, batch: int):
    """Native-ring closure for classification data (both sources).

    The closure takes ``wire`` ("f32"/"u8") and carries the matching
    device-side dequant constants: file images are stored as raw/255
    f32, so (qscale=255, qoff=0) makes the u8 wire re-ship the ORIGINAL
    dataset bytes losslessly; synthetic prototypes+noise use the
    (32, 4) affine covering [-4, 4).

    ``views=True`` yields zero-copy FeedItems for the device prefetcher
    (``supports_views`` advertises it; train.py's overlapped-feed path
    gates on the attribute). Ring depth/threads are sized from the round
    shape via ``plan_ring`` — the old fixed (4, 2) left ImageNet-sized
    slots permanently behind the consumer."""
    import numpy as np

    from consensusml_tpu.data import (
        native_file_round_batches,
        native_round_batches,
        plan_ring,
    )
    from consensusml_tpu.data.files import FileClassification

    if isinstance(data, FileClassification):
        fn, qp = native_file_round_batches, (255.0, 0.0)
    else:
        fn = native_round_batches
        qp = (SyntheticClassification.U8_QSCALE, SyntheticClassification.U8_QOFF)
    sample_floats = int(np.prod(data.image_shape))

    def make(rounds, seed, start=0, wire="f32", views=False, prefetch=2):
        # prefetch sizes the ring: every in-flight staged batch pins a
        # slot, so depth must exceed the prefetch window (plan_ring
        # keeps +2 slack) — train.py forwards --prefetch-depth here
        depth, nthreads = plan_ring(
            world * h * batch, sample_floats * (1 if wire == "u8" else 4),
            max(prefetch, 1),
        )
        return fn(
            data, world, h, batch, rounds, seed, start=start,
            depth=depth, nthreads=nthreads,
            wire=wire, qscale=qp[0], qoff=qp[1], views=views,
        )

    make.supports_wire = True  # train.py --native-wire gates on this
    make.supports_views = True  # train.py's overlapped feed gates on this
    make.qscale, make.qoff = qp
    return make


def _native_lm_batches(data, world: int, h: int, batch: int, *, mlm_rate: float = 0.0):
    """Native-ring closure for LM data (both sources)."""
    from consensusml_tpu.data import (
        native_file_token_batches,
        native_lm_round_batches,
    )
    from consensusml_tpu.data.files import TokenFileDataset

    fn = (
        native_file_token_batches
        if isinstance(data, TokenFileDataset)
        else native_lm_round_batches
    )
    return lambda rounds, seed, start=0: fn(
        data, world, h, batch, rounds, seed, mlm_rate=mlm_rate, start=start
    )


def _cls_eval_batches(data, batch: int):
    """Held-out classification batches from the dataset's holdout split.

    The holdout split materializes lazily on first use, so eval-less runs
    (and ``--list``) never pay for a second dataset copy."""
    import numpy as np

    def gen(n_batches: int, seed: int):
        held = data.holdout()
        for r in range(n_batches):
            rng = np.random.default_rng((seed + _EVAL_SEED_OFFSET, r))
            idx = rng.integers(0, held.n, size=batch)
            yield {
                "image": jnp.asarray(held.images[idx]),
                "label": jnp.asarray(held.labels[idx]),
            }

    return gen


def _mnist_mlp(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    from consensusml_tpu.models import MLP, mlp_loss_fn

    world = world or 4
    topo = topology_from_name("dense", world)
    model = MLP(hidden=256 if scale == "full" else 64)
    opt, base_lr = optax.adam, 1e-3
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo), optimizer=opt(base_lr), h=1
    )
    data = None
    if data_dir is not None:
        from consensusml_tpu.data.files import load_mnist

        data = load_mnist(data_dir)
    if data is None:
        data = SyntheticClassification(
            n=8192 if scale == "full" else 2048, image_shape=(28, 28, 1)
        )
    batch = 64
    return RunBundle(
        name="mnist_mlp",
        world_size=world,
        cfg=cfg,
        loss_fn=mlp_loss_fn(model),
        init_params=lambda r: model.init(r, jnp.zeros((1, 28, 28, 1)))["params"],
        batches=lambda rounds, seed, start=0: round_batches(data, world, cfg.h, batch, rounds, seed, start=start),
        description="2-layer MLP, 4 workers, dense gossip (CPU reference config)",
        eval_fn=classification_eval_fn(model),
        eval_batches=_cls_eval_batches(data, batch),
        native_batches=_native_cls_batches(data, world, cfg.h, batch),
        optimizer_factory=opt,
        base_lr=base_lr,
        model=model,
    )


def _cifar_resnet50(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    from consensusml_tpu.models import resnet50, resnet_init, resnet_loss_fn
    from consensusml_tpu.models.resnet import BottleneckBlock, ResNet

    world = world or 8
    topo = topology_from_name("ring", world)
    if scale == "full":
        model = resnet50(num_classes=10, stem="cifar")
        batch, image = 128, 32
    else:
        model = ResNet(
            stage_sizes=[1, 1], block=BottleneckBlock, num_classes=10, width=8,
            stem="cifar", dtype=jnp.float32,
        )
        batch, image = 8, 16
    opt = lambda lr: optax.sgd(lr, momentum=0.9)  # noqa: E731
    base_lr = 0.1 if scale == "full" else 0.05
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo),
        optimizer=opt(base_lr),
        h=1,
    )
    data = None
    if data_dir is not None:
        from consensusml_tpu.data.files import load_cifar10

        data = load_cifar10(data_dir)
        if data is not None:
            image = data.image_shape[0]  # real CIFAR is 32px at any scale
    if data is None:
        data = SyntheticClassification(
            n=4096 if scale == "full" else 512, image_shape=(image, image, 3), noise=0.25
        )
    return RunBundle(
        name="cifar_resnet50",
        world_size=world,
        cfg=cfg,
        loss_fn=resnet_loss_fn(model),
        init_params=resnet_init(model, (1, image, image, 3)),
        batches=lambda rounds, seed, start=0: round_batches(data, world, cfg.h, batch, rounds, seed, start=start),
        description="ResNet-50 (CIFAR stem), 8-worker ring consensus",
        eval_fn=classification_eval_fn(model, train_kwarg=True),
        eval_batches=_cls_eval_batches(data, batch),
        native_batches=_native_cls_batches(data, world, cfg.h, batch),
        optimizer_factory=opt,
        base_lr=base_lr,
        model=model,
    )


def _bert_mlm(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    from consensusml_tpu.models.bert import BertConfig, BertMLM, bert_mlm_loss_fn

    if scale == "full":
        world, model, batch, seq = world or 32, BertMLM(config=BertConfig()), 32, 128
        vocab = 30522
    else:
        world = world or 4
        vocab = 64
        model = BertMLM(
            config=BertConfig(
                vocab_size=vocab, hidden=32, layers=2, heads=2, mlp_dim=64,
                max_len=32, dropout=0.0,
            )
        )
        batch, seq = 8, 16
    topo = topology_from_name("ring", world)
    opt, base_lr = optax.adam, 1e-4 if scale == "full" else 1e-2
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo), optimizer=opt(base_lr), h=8
    )
    data = _file_tokens(data_dir, seq, vocab) or SyntheticLM(
        vocab_size=vocab, seq_len=seq
    )
    return RunBundle(
        name="bert_mlm",
        world_size=world,
        cfg=cfg,
        loss_fn=bert_mlm_loss_fn(model),
        init_params=lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
        batches=_lm_batches(data, world, cfg.h, batch, mlm_rate=0.15),
        description="BERT MLM, local-SGD H=8 + periodic ring averaging",
        eval_fn=mlm_eval_fn(model),
        eval_batches=_lm_eval_batches(data, batch, mlm_rate=0.15),
        native_batches=_native_lm_batches(data, world, cfg.h, batch, mlm_rate=0.15),
        optimizer_factory=opt,
        base_lr=base_lr,
        model=model,
    )


def _llama_lora(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    from consensusml_tpu.models.llama import llama2_7b, llama_tiny, llama_loss_fn
    from consensusml_tpu.models.lora import lora_gossip_filter, lora_mask, lora_optimizer
    from consensusml_tpu.parallel import llama_tp_rules

    if scale == "full":
        world = world or 16
        model = llama2_7b(lora_rank=16)
        batch, seq, vocab = 8, 2048, 32000
        # full 7B weights are ~14 GB bf16 / ~28 GB f32 per replica — a
        # single v5e chip's HBM (16 GB) cannot hold weights + activations,
        # so each worker defaults to a tp=4 submesh (4x4 torus x tp4 = 64
        # chips). Override per run with train.py --model-axes.
        model_axes: tuple = (("tp", 4),)
    else:
        world = world or 4
        model = llama_tiny(lora_rank=4)
        batch, seq, vocab = 8, 16, 256
        model_axes = ()
    topo = topology_from_name("torus", world)
    rows, cols = topo.mesh_shape
    def opt(lr, grad_clip: float = 0.0):
        # clip inside the LoRA mask: the global norm must be over the
        # trained adapters, not the frozen base weights whose gradients
        # lora_optimizer discards (they would dominate the norm)
        inner = optax.adam(lr)
        if grad_clip > 0:
            inner = optax.chain(optax.clip_by_global_norm(grad_clip), inner)
        return lora_optimizer(inner)

    base_lr = 1e-3 if scale == "full" else 1e-2
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo, path_filter=lora_gossip_filter),
        optimizer=opt(base_lr),
        h=1,
    )
    data = _file_tokens(data_dir, seq, vocab) or SyntheticLM(
        vocab_size=vocab, seq_len=seq
    )

    def init(rng):
        # shared "pretrained" base across workers (fixed key, not per-worker)
        base_rng = jax.random.key(42)
        params = model.init(base_rng, jnp.zeros((1, seq), jnp.int32))["params"]
        mask = lora_mask(params)
        leaves = jax.tree.leaves(params)
        keys = jax.random.split(rng, len(leaves))
        return jax.tree.unflatten(
            jax.tree.structure(params),
            [
                jax.random.normal(k, p.shape, p.dtype) * 0.02 if m else p
                for p, m, k in zip(leaves, jax.tree.leaves(mask), keys)
            ],
        )

    return RunBundle(
        name="llama_lora",
        world_size=world,
        cfg=cfg,
        loss_fn=llama_loss_fn(model),
        init_params=init,
        batches=_lm_batches(data, world, cfg.h, batch),
        description=f"Llama LoRA fine-tune, {rows}x{cols} torus gossip (adapters-only wire)",
        eval_fn=causal_lm_eval_fn(model, deterministic_kwarg=False),
        eval_batches=_lm_eval_batches(data, batch),
        model_axes=model_axes,
        tp_rules=llama_tp_rules,
        native_batches=_native_lm_batches(data, world, cfg.h, batch),
        optimizer_factory=opt,
        base_lr=base_lr,
        model=model,
    )


def _gpt2_topk(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    from consensusml_tpu.models.gpt2 import GPT2Config, GPT2LM, gpt2_loss_fn
    from consensusml_tpu.parallel import gpt2_tp_rules

    if scale == "full":
        world = world or 8
        model = GPT2LM(config=GPT2Config())  # gpt2-medium dims
        batch, seq, vocab = 8, 1024, 50257
    else:
        world = world or 4
        vocab = 64
        model = GPT2LM(
            config=GPT2Config(
                vocab_size=vocab, hidden=32, layers=2, heads=2, max_len=32, dropout=0.0
            )
        )
        batch, seq = 8, 16
    topo = topology_from_name("ring", world)
    opt, base_lr = optax.adam, 1e-4 if scale == "full" else 3e-3
    cfg = LocalSGDConfig(
        gossip=GossipConfig(
            topology=topo,
            # Pallas-kernel-backed codec pair: chunked top-k + fused int8
            # (impl="auto": compiled kernels on TPU, identical jnp math on
            # the CPU test mesh). full: 8 of 512 per chunk ~= ratio 1/64;
            # smoke: 13 of 128 ~= the 0.1 ratio the tests converge with.
            compressor=(
                topk_int8_compressor(chunk=512, k=8, impl="auto")
                if scale == "full"
                else topk_int8_compressor(ratio=0.1, chunk=128, impl="auto")
            ),
            # RECALIBRATED for scale (r4 frontier study, 30M-param decoder
            # + Adam, docs/convergence.md): the r3 full recipe (gamma 0.5,
            # cold start) diverges outright — 1/64 top-k cannot track
            # Adam's early violence and error feedback on never-shipped
            # coordinates leaks without bound. Shipped full recipe:
            # gamma 0.1 + 50 exact warmup rounds + a dense refresh every
            # 50 rounds lands the consensus model within 2.5% held-out
            # NLL of exact gossip with steady-state wire ~31x dense
            # (top-k payload + dense/50 amortized). Smoke keeps the
            # tested 0.1-ratio recipe at gamma 0.5.
            gamma=0.1 if scale == "full" else 0.5,
            codec_warmup_rounds=50 if scale == "full" else 0,
            codec_refresh_every=50 if scale == "full" else 0,
        ),
        optimizer=opt(base_lr),
        h=2,
    )
    data = _file_tokens(data_dir, seq, vocab) or SyntheticLM(
        vocab_size=vocab, seq_len=seq
    )
    return RunBundle(
        name="gpt2_topk",
        world_size=world,
        cfg=cfg,
        loss_fn=gpt2_loss_fn(model),
        init_params=lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
        batches=_lm_batches(data, world, cfg.h, batch),
        description="GPT-2 pretrain with top-k + int8 compressed gossip (CHOCO)",
        eval_fn=causal_lm_eval_fn(model),
        eval_batches=_lm_eval_batches(data, batch),
        tp_rules=gpt2_tp_rules,  # opt-in via train.py --model-axes tp=N
        native_batches=_native_lm_batches(data, world, cfg.h, batch),
        optimizer_factory=opt,
        base_lr=base_lr,
        model=model,
    )


def _held_share_bundle(name, description, model, scale, batch, seq, data_dir, world) -> RunBundle:
    """What the one-chip-share-of-an-expert-parallel-deployment recipes have in common: the pattern
    decoder's loss, Adam, exact gossip on a ring of ``world``, ``h`` = 2."""
    from consensusml_tpu.models.nemotron_h import nemotron_h_loss_fn

    vocab = model.config.vocab_size
    topo = topology_from_name("ring", world)
    # full: the step size warms up linearly from 0 (`--warmup-rounds` restates
    # it). Adam's first steps move every weight by the whole step size, which
    # at 1e-4 throws a 2,688-wide router's load about by half a step; no run
    # starts so, and the rounds a benchmark times are the warm-up's first
    opt, base_lr = optax.adam, 1e-4 if scale == "full" else 3e-3
    warmup_steps = 20_000 if scale == "full" else 0
    cfg = LocalSGDConfig(
        gossip=GossipConfig(topology=topo, compressor=None),
        optimizer=build_optimizer(opt, peak_lr=base_lr, warmup_steps=warmup_steps),
        h=2,
    )
    data = _file_tokens(data_dir, seq, vocab) or SyntheticLM(vocab_size=vocab, seq_len=seq)
    return RunBundle(
        name=name,
        world_size=world,
        cfg=cfg,
        loss_fn=nemotron_h_loss_fn(model),
        init_params=lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
        batches=_lm_batches(data, world, cfg.h, batch),
        description=description,
        native_batches=_native_lm_batches(data, world, cfg.h, batch),
        optimizer_factory=opt,
        base_lr=base_lr,
        base_warmup_steps=warmup_steps,
        model=model,
    )


def _nemotron_h_ep16(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    """Nemotron 3 Nano's hybrid block, cut to what ONE chip of an EP-16
    deployment holds: the first nine blocks ``MEMEM*EME`` (a pipeline stage),
    8 of the 128 routed experts of each ``E`` block (rank 0 of the 16 chips
    that share a layer), an eighth of the vocabulary, every width as
    published; 667.0M parameters. Exact gossip: CHOCO's float32 tracking
    state (two more copies of the parameters) does not fit beside Adam's on a
    share this large (ROADMAP B-i). Nothing stands in for the 15 absent chips
    or their all-to-all."""
    from consensusml_tpu.models.nemotron_h import NemotronHConfig, NemotronHLM, nemotron_h_tiny

    if scale == "full":
        model = NemotronHLM(
            config=NemotronHConfig(
                vocab_size=16384, pattern="MEMEM*EME", held=8, held_start=0,
                remat=True, loss_vocab_chunk=4096, score_correction="centred",
            )
        )
        batch, seq = 1, 8192
    else:
        model = nemotron_h_tiny(score_correction="centred")
        batch, seq = 2, 32
    return _held_share_bundle(
        "nemotron_h_ep16",
        "hybrid Mamba-2 / expert / attention decoder, one chip's share of EP-16, exact gossip",
        model, scale, batch, seq, data_dir, world or 1,
    )


def _qwen3_next_ep16(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    """Qwen3-Next-80B-A3B's layer, cut to what ONE chip of an EP-16
    deployment holds: one period of four layers (three Gated DeltaNet mixers,
    one gated attention, an expert layer after each: ``GEGEGEAE``, a pipeline
    stage), 32 of the 512 routed experts of each expert layer (rank 0 of the 16
    chips that share a layer), an eighth of the vocabulary, every width as
    published; 625.7M parameters. Exact gossip, the warm-up and the centred
    score correction for the reasons :func:`_nemotron_h_ep16` gives. Nothing
    stands in for the 15 absent chips or their all-to-all."""
    from consensusml_tpu.models.nemotron_h import qwen3_next_share, qwen3_next_tiny

    if scale == "full":
        model = qwen3_next_share(remat=True, loss_vocab_chunk=4748, score_correction="centred")
        batch, seq = 1, 8192
    else:
        model = qwen3_next_tiny(score_correction="centred")
        batch, seq = 2, 32
    return _held_share_bundle(
        "qwen3_next_ep16",
        "Gated DeltaNet / gated attention / 512-expert decoder, one chip's share of EP-16, exact gossip",
        model, scale, batch, seq, data_dir, world or 1,
    )


def _xing4_ep8(scale: str, data_dir: str | None = None, world: int | None = None) -> RunBundle:
    """Xing4.0-29B-A4B's layer, cut to what ONE chip of an EP-8 deployment
    holds: five layers (a leading dense one and four expert layers, latent
    attention in each: ``LDLELELELE``, a pipeline stage) on four hyper-connected
    residual streams, 8 of the 64 routed experts of each expert layer (rank 0
    of the 8 chips that share a layer), an eighth of the vocabulary, the
    multi-token-prediction module (one more expert layer, its loss weighted
    0.3), every width as published; 913.5M parameters, rows of 4,096 tokens
    (the source's ``original_max_position_embeddings``; 8,192 do not fit beside
    the module). Exact gossip, the warm-up and the centred score correction
    for the reasons :func:`_nemotron_h_ep16` gives. Nothing stands in for the
    7 absent chips or their all-to-all."""
    from consensusml_tpu.models.nemotron_h import xing4_share, xing4_tiny

    if scale == "full":
        model = xing4_share(remat=True, loss_vocab_chunk=4096, score_correction="centred")
        batch, seq = 1, 4096
    else:
        model = xing4_tiny(score_correction="centred")
        batch, seq = 2, 32
    return _held_share_bundle(
        "xing4_ep8",
        "latent attention / four hyper-connected streams / 64-expert decoder with a "
        "multi-token-prediction module, one chip's share of EP-8, exact gossip",
        model, scale, batch, seq, data_dir, world or 1,
    )


CONFIGS = {
    "nemotron_h_ep16": _nemotron_h_ep16,
    "qwen3_next_ep16": _qwen3_next_ep16,
    "xing4_ep8": _xing4_ep8,
    "mnist_mlp": _mnist_mlp,
    "cifar_resnet50": _cifar_resnet50,
    "bert_mlm": _bert_mlm,
    "llama_lora": _llama_lora,
    "gpt2_topk": _gpt2_topk,
}


def names() -> list[str]:
    return sorted(CONFIGS)


def build(
    name: str,
    scale: str = "smoke",
    data_dir: str | None = None,
    world: int | None = None,
) -> RunBundle:
    """Build a workload bundle. ``world`` overrides the config's worker
    count (topology is rebuilt at that size; torus refactors to the
    squarest grid) — the CLI's ``--workers`` and elastic resume use it."""
    if name not in CONFIGS:
        raise ValueError(f"unknown config {name!r}; available: {names()}")
    if scale not in ("smoke", "full"):
        raise ValueError(f"scale must be smoke|full, got {scale!r}")
    if world is not None and world < 1:
        raise ValueError(f"world must be positive, got {world}")
    return CONFIGS[name](scale, data_dir, world)
