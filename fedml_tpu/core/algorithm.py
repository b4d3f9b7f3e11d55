"""Functional federated-algorithm contract + shared local-training machinery.

TPU-native replacement for the reference operator ABCs:
- `ClientTrainer.train()` (reference: core/alg_frame/client_trainer.py:52 — a
  stateful torch loop) becomes `client_update`: a pure function
  (broadcast, shard, client_state, rng) -> (update, new_state, metrics) whose
  inner SGD loop is `lax.scan` over batch indices, so the whole local epoch
  compiles into one XLA program.
- `ServerAggregator.aggregate()` (reference: core/alg_frame/server_aggregator.py:67)
  becomes `server_update`: (ServerState, aggregated_update) -> ServerState.
- Aggregation itself is declared, not executed, by the algorithm: LINEAR means
  "weighted mean, psum-able over a mesh axis"; FULL means "needs every client
  update materialized" (robust defenses like Krum). The round engine
  (parallel/round.py) picks collectives accordingly.

Lifecycle hooks (`on_before/after_local_training`, `on_before/on/after_
aggregation` — reference: server_aggregator.py:42-83, client_trainer.py:32-59)
are composable pytree transforms whose sites live in the round engine
(parallel/round.py: postprocess_update / aggregate_full / postprocess_agg,
composed by simulation/simulator.py), so DP/security/compression stay
plugins, not forks (SURVEY.md §7.3).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..ops import tree as tu

Pytree = Any

# Aggregation modes
LINEAR = "linear"   # update aggregates as a sample-count-weighted mean (psum)
FULL = "full"       # aggregator needs the full stacked update set (all_gather)


@struct.dataclass
class ServerState:
    """Global state carried across rounds. `extra` holds algorithm-specific
    state (SCAFFOLD's c, FedDyn's h, Mime's broadcast optimizer state...)."""
    params: Pytree
    opt_state: Any
    round: jax.Array
    extra: Any = None


@struct.dataclass
class ClientMetrics:
    """Linear-aggregable training metrics (sums, not means). `extra`: what
    the model counted of itself on the way ({name: number}, summed over the
    local steps and then over the clients like the rest), None for a model
    that counts nothing."""
    loss_sum: jax.Array
    correct: jax.Array
    count: jax.Array
    extra: Any = None


def masked_softmax_ce(logits: jax.Array, y: jax.Array, mask: jax.Array):
    """Cross-entropy over a padded batch. Returns (loss_mean, correct, count).
    Padding rows (mask=0) contribute nothing; a fully-padded batch yields 0
    loss and 0 gradient, so SPMD-padded clients train correctly."""
    if logits.ndim == 3:  # sequence model: [B, T, V] vs y [B, T]
        logits = logits.reshape(-1, logits.shape[-1])
        y = y.reshape(-1)
        mask = jnp.repeat(mask, logits.shape[0] // mask.shape[0])
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (ce * mask).sum() / denom
    correct = ((jnp.argmax(logits, -1) == y) * mask).sum()
    return loss, correct, mask.sum()


NWP_PAD_ID = 0  # reference: nn.CrossEntropyLoss(ignore_index=0)


def nwp_softmax_ce(logits: jax.Array, y: jax.Array, mask: jax.Array):
    """Next-word-prediction head: per-token CE that excludes pad targets.

    The reference trains NWP with `nn.CrossEntropyLoss(ignore_index=0)` and
    masks accuracy the same way (ml/trainer/my_model_trainer_nwp.py:24,75), so
    a pad token (id 0) anywhere in a real sequence contributes to neither loss
    nor accuracy. The per-token mask is the per-sample pad mask [B] crossed
    with (y != pad_id) [B, T]; padded rows have all-zero targets, so the
    sample mask is subsumed but kept for clarity under SPMD padding.
    """
    tok = (mask[:, None] * (y != NWP_PAD_ID)).astype(logits.dtype).reshape(-1)
    logits = logits.reshape(-1, logits.shape[-1])
    y = y.reshape(-1)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    denom = jnp.maximum(tok.sum(), 1.0)
    loss = (ce * tok).sum() / denom
    correct = ((jnp.argmax(logits, -1) == y) * tok).sum()
    return loss, correct, tok.sum()


def masked_mse(pred: jax.Array, y: jax.Array, mask: jax.Array):
    """Regression objective: mean squared error over a padded batch;
    'correct' reports predictions within 0.5 of the target so the engine's
    accuracy plumbing stays meaningful (reference: the regression trainers
    report MSE/MAE — ml/trainer/my_model_trainer_regression.py)."""
    if pred.ndim == 2 and pred.shape[-1] == 1:
        pred = pred[:, 0]
    err = (pred - y.astype(pred.dtype)) ** 2
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (err * mask).sum() / denom
    close = ((jnp.abs(pred - y) < 0.5) * mask).sum()
    return loss, close, mask.sum()


def masked_bce_multilabel(logits: jax.Array, y: jax.Array, mask: jax.Array):
    """Multi-label objective (stackoverflow_lr tag prediction — reference:
    data/stackoverflow_lr + lr trainer with BCE): y is a [B, L] multi-hot
    matrix; 'correct' counts per-label hits so acc = label-wise accuracy."""
    yf = y.astype(logits.dtype)
    bce = optax.sigmoid_binary_cross_entropy(logits, yf).mean(-1)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (bce * mask).sum() / denom
    hits = (((logits > 0) == (yf > 0.5)).mean(-1) * mask).sum()
    return loss, hits, mask.sum()


SEG_IGNORE_ID = 255  # reference: fedseg trainers pass ignore_index=255


def seg_softmax_ce(logits: jax.Array, y: jax.Array, mask: jax.Array):
    """Segmentation head: per-pixel CE with an ignore label (FedSeg parity —
    reference: simulation/mpi/fedseg/utils.py SegmentationLosses builds
    nn.CrossEntropyLoss(ignore_index=255)). logits [B, H, W, C], y
    [B, H, W] int labels; the per-pixel weight is the per-sample pad mask
    [B] crossed with (y != 255), so SPMD-padded samples and ignore pixels
    contribute to neither loss nor pixel accuracy."""
    valid = y != SEG_IGNORE_ID
    pix = (mask[:, None, None] * valid).astype(jnp.float32)
    # ignore pixels get a safe in-range label; their CE is masked out anyway
    y_safe = jnp.where(valid, y, 0)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, y_safe)
    denom = jnp.maximum(pix.sum(), 1.0)
    loss = (ce * pix).sum() / denom
    correct = ((jnp.argmax(logits, -1) == y_safe) * pix).sum()
    return loss, correct, pix.sum()


def _seg_confusion(logits: jax.Array, y: jax.Array, num_classes: int,
                   mask: jax.Array | None, ignore_id: int) -> jax.Array:
    """[true, pred] pixel confusion matrix over valid pixels (ignore-label
    and SPMD-padded samples excluded). Jit-safe: one-hot matmul, no
    data-dependent shapes. Additive across batches, so whole-set metrics
    accumulate it (seg_eval_fn) and one-shot metrics use it directly."""
    pred = jnp.argmax(logits, -1)
    valid = (y != ignore_id)
    if mask is not None:
        valid = valid & (mask[:, None, None] > 0)
    vf = valid.reshape(-1).astype(jnp.float32)
    py = jax.nn.one_hot(y.reshape(-1), num_classes) * vf[:, None]
    pp = jax.nn.one_hot(pred.reshape(-1), num_classes) * vf[:, None]
    return py.T @ pp


def _iou_from_confusion(confusion: jax.Array):
    """(miou, per_class_iou); classes absent from both prediction and
    target are excluded from the mean."""
    inter = jnp.diagonal(confusion)
    union = confusion.sum(0) + confusion.sum(1) - inter
    present = union > 0
    iou = jnp.where(present, inter / jnp.maximum(union, 1.0), 0.0)
    return iou.sum() / jnp.maximum(present.sum(), 1), iou


def miou_from_logits(logits: jax.Array, y: jax.Array, num_classes: int,
                     mask: jax.Array | None = None,
                     ignore_id: int = SEG_IGNORE_ID):
    """Mean intersection-over-union, the FedSeg eval metric (reference:
    fedseg/utils.py Evaluator.Mean_Intersection_over_Union — confusion-
    matrix based). Returns (miou, per_class_iou)."""
    return _iou_from_confusion(
        _seg_confusion(logits, y, num_classes, mask, ignore_id))


# default-aggregator task heads (VERDICT: reference ships classification,
# NWP, and regression aggregator variants — ml/aggregator/; segmentation
# closes the FedSeg runtime row, simulation/mpi/fedseg/FedSegAPI.py:1)
OBJECTIVES = {
    "classification": masked_softmax_ce,
    "nwp": nwp_softmax_ce,             # pad targets (id 0) excluded, ref parity
    "regression": masked_mse,
    "multilabel": masked_bce_multilabel,
    "segmentation": seg_softmax_ce,    # per-pixel CE, ignore label 255
}


def make_objective(task: Optional[str]):
    t = (task or "classification").lower()
    if t not in OBJECTIVES:
        raise ValueError(f"unknown task {t!r}; choose from "
                         f"{sorted(OBJECTIVES)}")
    return OBJECTIVES[t]


def make_batch_indices(rng: jax.Array, shard_size: int, batch_size: int, epochs: int):
    """Per-epoch permutations of a padded shard, reshaped to [epochs*nb, B].
    Equivalent to the reference's shuffling DataLoader per local epoch
    (reference: ml/trainer/my_model_trainer_classification.py:43)."""
    bs = min(batch_size, shard_size)
    nb = shard_size // bs
    perms = jax.vmap(lambda r: jax.random.permutation(r, shard_size))(
        jax.random.split(rng, epochs)
    )
    # truncate the tail when bs doesn't divide shard_size (user-supplied
    # FedDatasets aren't necessarily padded to a batch multiple)
    return perms[:, : nb * bs].reshape(epochs * nb, bs)


def make_client_optimizer(name: str, lr: float, momentum: float = 0.0,
                          weight_decay: float = 0.0) -> optax.GradientTransformation:
    """Client-side optimizer factory (reference: my_model_trainer_classification.py:30
    builds torch SGD/Adam from args.client_optimizer)."""
    txs = []
    if weight_decay:
        txs.append(optax.add_decayed_weights(weight_decay))
    name = name.lower()
    if name == "sgd":
        txs.append(optax.sgd(lr, momentum=momentum if momentum else None))
    elif name == "adam":
        txs.append(optax.adam(lr))
    elif name == "adamw":
        return optax.adamw(lr, weight_decay=weight_decay)
    else:
        raise ValueError(f"unknown client_optimizer {name!r}")
    return optax.chain(*txs)


def local_sgd(
    apply_fn: Callable,
    params: Pytree,
    shard: dict,                     # {"x": [S,...], "y": [S], "mask": [S]}
    batch_idx: jax.Array,            # [num_steps, B] int32
    opt: optax.GradientTransformation,
    grad_correction: Optional[Callable[[Pytree, Pytree], Pytree]] = None,
    objective: Optional[Callable] = None,
    opt_state: Optional[Any] = None,
    return_opt_state: bool = False,
) -> tuple[Pytree, ClientMetrics, jax.Array]:
    """The hot loop: lax.scan over batches; grads of the masked CE loss;
    optional per-step gradient correction (FedProx prox term, SCAFFOLD control
    variates, FedDyn linear terms — all are `g + f(params)` shapes).

    Returns (final_params, summed_metrics, effective_steps) where
    effective_steps counts batches containing >=1 real sample — FedNova's
    tau_i under padding.
    """
    if opt_state is None:
        opt_state = opt.init(params)
    obj = objective or masked_softmax_ce

    def loss_fn(p, batch):
        # an apply fn may return (logits, {name: number}): what the model
        # counted of itself in this call (llm.federated_lora)
        out = apply_fn({"params": p}, batch["x"])
        logits, extra = out if isinstance(out, tuple) else (out, None)
        loss, correct, cnt = obj(logits, batch["y"], batch["mask"])
        return loss, (correct, cnt, extra)

    def step(carry, batch):
        p, s = carry
        (loss, (correct, cnt, extra)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, batch)
        if grad_correction is not None:
            grads = grad_correction(grads, p)
        updates, s = opt.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        nonempty = (cnt > 0).astype(jnp.float32)
        return (p, s), (loss * cnt, correct, cnt, nonempty, extra)

    (params, opt_state), (losses, corrects, counts, steps, extras) = jax.lax.scan(
        lambda carry, idx: step(
            carry, {k: v[idx] for k, v in shard.items()}),
        (params, opt_state), batch_idx,
    )
    metrics = ClientMetrics(losses.sum(), corrects.sum(), counts.sum(),
                            jax.tree.map(lambda a: a.sum(0), extras))
    if return_opt_state:
        return params, metrics, steps.sum(), opt_state
    return params, metrics, steps.sum()


@dataclasses.dataclass(frozen=True)
class FedAlgorithm:
    """The pluggable federated-optimizer contract (one instance per algorithm;
    registered in core.registry.ALGORITHMS by name, matching the reference's
    `federated_optimizer` config values)."""
    name: str
    server_init: Callable[[Pytree, Any], ServerState]
    client_update: Callable[..., tuple[Pytree, Pytree, ClientMetrics]]
    server_update: Callable[[ServerState, Pytree], ServerState]
    # broadcast: what clients see. Default: current global params + extra.
    broadcast: Callable[[ServerState], dict] = None  # type: ignore[assignment]
    # per-client persistent state (stacked [num_clients, ...] by the engine)
    client_state_init: Optional[Callable[[Pytree], Pytree]] = None
    agg_mode: str = LINEAR

    def __post_init__(self):
        if self.broadcast is None:
            object.__setattr__(
                self, "broadcast",
                lambda st: {"params": st.params, "extra": st.extra},
            )


def seg_eval_fn(apply_fn: Callable, num_classes: int,
                ignore_id: int = SEG_IGNORE_ID):
    """Segmentation eval: batched jittable pass returning loss, pixel acc,
    AND mIoU — the FedSeg server-side metric (reference: fedseg/utils.py
    Evaluator; the confusion matrix accumulates across batches so the mIoU
    is over the whole set, not a mean of per-batch IoUs)."""

    @jax.jit
    def eval_batches(params, x, y, mask):
        def one(conf, batch):
            logits = apply_fn({"params": params}, batch["x"])
            loss, correct, cnt = seg_softmax_ce(
                logits, batch["y"], batch["mask"])
            conf = conf + _seg_confusion(
                logits, batch["y"], num_classes, batch["mask"], ignore_id)
            return conf, (loss * cnt, correct, cnt)

        conf, (l, c, n) = jax.lax.scan(
            one, jnp.zeros((num_classes, num_classes), jnp.float32),
            {"x": x, "y": y, "mask": mask})
        miou, iou = _iou_from_confusion(conf)
        n_tot = jnp.maximum(n.sum(), 1.0)
        return {"loss": l.sum() / n_tot, "acc": c.sum() / n_tot,
                "miou": miou, "per_class_iou": iou, "n": n.sum()}

    return eval_batches


def make_eval_fn(apply_fn: Callable, task: Optional[str] = None,
                 num_classes: Optional[int] = None):
    """Task-aware eval factory — ONE dispatch shared by every engine
    (Simulator, AsyncSimulator, centralized Trainer), so a segmentation
    config gets the whole-set confusion-matrix evaluator (mIoU rides the
    eval row) everywhere instead of only where someone special-cased it.
    Returns eval(params, x, y, mask) over batched test arrays."""
    if (task or "").lower() == "segmentation":
        if num_classes is None:
            raise ValueError(
                "segmentation eval needs num_classes (the confusion matrix "
                "shape)")
        return seg_eval_fn(apply_fn, num_classes)
    return jax.jit(eval_step_fn(apply_fn, make_objective(task)))


def eval_step_fn(apply_fn: Callable, objective: Optional[Callable] = None):
    """Batched, jittable eval over the global test set (reference:
    `test_on_server_for_all_clients`, cross_silo/server/fedml_aggregator.py).
    `objective` picks the task head (classification default; regression /
    multilabel / nwp via make_objective)."""
    obj = objective or masked_softmax_ce

    def eval_batches(params, x, y, mask):
        def one(carry, batch):
            loss, correct, cnt = obj(
                apply_fn({"params": params}, batch["x"]), batch["y"], batch["mask"]
            )
            return carry, (loss * cnt, correct, cnt)

        _, (l, c, n) = jax.lax.scan(one, 0, {"x": x, "y": y, "mask": mask})
        n_tot = jnp.maximum(n.sum(), 1.0)
        return {"loss": l.sum() / n_tot, "acc": c.sum() / n_tot, "n": n.sum()}

    return eval_batches
