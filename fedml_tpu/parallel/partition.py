"""One partitioning plane: regex-rule shardings for train AND serve.

The proven pattern (SNIPPETS.md exemplars; the same shape FedJAX-style
systems use to scale past one device): a *rule table* — an ordered sequence
of `(param-name regex, PartitionSpec)` pairs — plus
`match_partition_rules(rules, params)` resolving every leaf of a param
pytree to a spec over a named device mesh. This module is the SINGLE source
of truth for how parameters get shardings in this repo:

- `llm/tp.py` (`tp_param_specs`) is a thin shim over the
  `transformer_lm` table,
- the federated round programs consume it (`parallel/round.py
  shard_fed_data` / `resolve_param_specs`),
- the `CentralizedTrainer` shards its params through it when
  `device_args.mesh_shape` names an `mp` axis,
- the serving `DecodeEngine` shards its weights AND its persistent KV
  page pool through it (`paged_kv_cache_spec`) to run tensor-parallel.

Train and serve resolving through ONE table is what keeps checkpoints
mesh-compatible across the two planes (a silently different serve layout is
how train/serve checkpoint drift starts).

Policies (both are contracts, not conveniences):
- a param matching two rules with DIFFERENT specs is a HARD error
  (`AmbiguousRuleError`): first-match-silently-wins is exactly how two
  tables drift apart without anyone noticing;
- an UNMATCHED param is a hard error by default (`UnmatchedParamError`);
  pass `on_unmatched="replicated"` to opt into replication (the shim does,
  for backward compatibility with the old heuristic).

Mesh axis conventions: `dp` (data/batch), `mp` (model/tensor parallel —
Megatron column/row over the `mp` axis), `clients` (federated-parallel),
plus `silos`/`intra`/`seq` for the hierarchical and sequence planes.

Import stays jax-free (lazy imports inside functions) so config.py can
validate `device_args.partition_rules` at load without dragging in the
runtime — the same contract the chaos/retry specs follow.

Use `explain(rules, params)` to print the resolved table when debugging a
layout.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence

Pytree = Any
# (regex, PartitionSpec) pairs; re.search semantics over '/'-joined paths
Rules = Sequence[tuple]

ERROR = "error"
REPLICATED = "replicated"


class PartitionRuleError(ValueError):
    """A rule table failed to load or resolve against a param tree."""


class AmbiguousRuleError(PartitionRuleError):
    """One param matched two rules with different specs — a hard error:
    whichever rule "wins" silently is how train and serve layouts drift."""


class UnmatchedParamError(PartitionRuleError):
    """A param matched no rule under the default `on_unmatched="error"`
    policy."""


def path_name(path) -> str:
    """'/'-joined leaf path — the name the rule regexes match against."""
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _compile(rules: Rules) -> list:
    """Validate + compile a rule table ("registry load" checks): regexes
    must compile, and the SAME pattern listed twice with different specs is
    ambiguous on its face (no params needed to see it)."""
    seen: dict = {}
    out = []
    for pattern, spec in rules:
        try:
            rx = re.compile(pattern)
        except re.error as e:
            raise PartitionRuleError(
                f"partition rule {pattern!r} is not a valid regex: {e}"
            ) from None
        if pattern in seen and seen[pattern] != tuple(spec):
            raise AmbiguousRuleError(
                f"rule table lists pattern {pattern!r} twice with "
                f"different specs ({seen[pattern]} vs {tuple(spec)})")
        seen[pattern] = tuple(spec)
        out.append((pattern, rx, spec))
    return out


def match_partition_rules(rules: Rules, params: Pytree, *,
                          on_unmatched: str = ERROR) -> Pytree:
    """Resolve a param pytree to a same-structure tree of PartitionSpecs.

    Every leaf's '/'-joined path is matched against ALL rules
    (`re.search`); scalars and size-1 leaves resolve to replicated without
    consulting the table (nothing to partition). Matching two rules with
    different specs raises `AmbiguousRuleError`; matching none raises
    `UnmatchedParamError` unless `on_unmatched="replicated"`. A spec with
    more axes than the leaf has dims is also refused here — downstream it
    surfaces as an opaque NamedSharding error far from the bad rule.
    """
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    if on_unmatched not in (ERROR, REPLICATED):
        raise ValueError(
            f"on_unmatched must be {ERROR!r} or {REPLICATED!r}; "
            f"got {on_unmatched!r}")
    compiled = _compile(rules)

    def spec_for(path, leaf):
        name = path_name(path)
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        hits = [(pat, spec) for pat, rx, spec in compiled
                if rx.search(name) is not None]
        distinct = {tuple(spec) for _pat, spec in hits}
        if len(distinct) > 1:
            detail = "; ".join(f"{pat!r} -> {spec}" for pat, spec in hits)
            raise AmbiguousRuleError(
                f"param {name!r} matches rules with different specs: "
                f"{detail}")
        if not hits:
            if on_unmatched == REPLICATED:
                return P()
            raise UnmatchedParamError(
                f"no partition rule matches param {name!r} (shape "
                f"{shape}); add a rule or pass "
                f"on_unmatched='replicated' to replicate unmatched params")
        spec = hits[0][1]
        if len(spec) > len(shape):
            raise PartitionRuleError(
                f"rule {hits[0][0]!r} assigns {len(spec)}-axis spec "
                f"{spec} to param {name!r} of rank {len(shape)}")
        return spec

    return jax.tree_util.tree_map_with_path(spec_for, params)


def explain(rules: Rules, params: Pytree, *,
            on_unmatched: str = ERROR) -> str:
    """Human-readable resolved table: one line per param with its shape,
    resolved spec, and the rule that produced it ('<scalar>' for the
    size-1 fast path, '<unmatched>' under the replicated policy). The
    debugging surface for "why is this leaf laid out like that"."""
    import jax

    rules = rules_for(rules) if isinstance(rules, str) else rules
    compiled = _compile(rules)
    specs = match_partition_rules(rules, params, on_unmatched=on_unmatched)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree_util.tree_leaves(specs)
    lines = []
    for (path, leaf), spec in zip(flat_p, flat_s):
        name = path_name(path)
        src = next((pat for pat, rx, s in compiled
                    if rx.search(name) is not None and tuple(s) == tuple(spec)),
                   None)
        shape = tuple(getattr(leaf, "shape", ()))
        lines.append(f"{name:<44} {str(shape):<20} -> {str(spec):<24} "
                     f"[{src if src is not None else '<unmatched/scalar>'}]")
    return "\n".join(lines)


# --------------------------------------------------------------- rule tables
_COL = r"wq|wk|wv|w_gate|w_up"   # Megatron column split: shard OUTPUT features
_ROW = r"wo|w_down"              # Megatron row split:    shard INPUT features


def transformer_lm_rules(axis: str = "mp") -> Rules:
    """The flagship TransformerLM table (llm/transformer.py), Megatron
    column-then-row layout over `axis` — one all-reduce per attention
    output and one per MLP, inserted by GSPMD. Covers all three base
    layouts: unrolled 2-D kernels (`block_i/...`), scan-over-layers
    stacked 3-D kernels (`blocks/...`, leading [L] axis replicated), and
    int8-quantized `{q, s}` leaves (`q` shards like the kernel it stores;
    per-dout scales `s` shard alongside column kernels and replicate for
    row kernels, whose split dim is din), and the decode engine's one
    kernel for the three column-split projections (`wqkv`). Embed [V, D]
    shards D, lm_head [D, V] shards V; norms replicated. LoRA adapters are
    NOT in this table — they are the federated round payload and resolve
    through `lora_rules` (replicated)."""
    from jax.sharding import PartitionSpec as P

    a = axis
    return (
        # unrolled blocks: kernel/q [din, dout], scales s [1, dout]
        (rf"(^|/)block_\d+/({_COL})/kernel(/(q|s))?$", P(None, a)),
        (rf"(^|/)block_\d+/({_ROW})/kernel(/q)?$", P(a, None)),
        (rf"(^|/)block_\d+/({_ROW})/kernel/s$", P()),
        # scan-layers stacked blocks: [L, din, dout], scales [L, 1, dout]
        (rf"(^|/)blocks/({_COL})/kernel(/(q|s))?$", P(None, None, a)),
        (rf"(^|/)blocks/({_ROW})/kernel(/q)?$", P(None, a, None)),
        (rf"(^|/)blocks/({_ROW})/kernel/s$", P()),
        # the decode engine's wq|wk|wv (llm/decode.py lay_out_blocks):
        # [L, din, groups, cols], scales [L, 1, groups, cols], a column
        # group a device
        (r"(^|/)blocks/wqkv/kernel(/(q|s))?$", P(None, None, a, None)),
        # embed [V, D] shards D; lm_head [D, V] shards V. Their int8
        # scales are HBM-negligible and stay replicated (the llm/tp.py
        # legacy layout, kept so existing sharded checkpoints reload).
        (r"(^|/)embed/embedding(/q)?$", P(None, a)),
        (r"(^|/)embed/embedding/s$", P()),
        (r"(^|/)lm_head/kernel(/q)?$", P(None, a)),
        (r"(^|/)lm_head/kernel/s$", P()),
        # norms replicated — [D] unrolled, [L, D] stacked (size-1 rule
        # would not cover these: D > 1)
        (r"(^|/)RMSNorm_\d+/scale$", P()),
        (r"(^|/)final_norm/scale$", P()),
    )


def mlp_cnn_rules(axis: str = "mp") -> Rules:
    """MLP / CNN workloads (models/cv.py, models/hub.py): Dense kernels
    [din, dout] column-split on dout, conv kernels [kh, kw, cin, cout]
    split on cout, biases and norm scales replicated. Anything exotic
    (depthwise stacks, squeeze-excite) falls to the unmatched policy —
    pass `on_unmatched="replicated"` for models this table only partially
    covers, or extend the table."""
    from jax.sharding import PartitionSpec as P

    a = axis
    return (
        (r"(^|/)Dense_\d+/kernel$", P(None, a)),
        (r"(^|/)Conv_\d+/kernel$", P(None, None, None, a)),
        (r"(/|^)(bias|scale)$", P()),
        (r"embedding$", P(None, a)),
    )


def lora_rules(axis: str = "mp") -> Rules:
    """LoRA adapter trees (llm/lora.py `{path: {"a", "b"}}`): REPLICATED.
    Adapters are the federated round payload — every client/chip holds and
    exchanges the full tree while only the frozen base is mp-sharded
    (`axis` accepted for signature uniformity; unused)."""
    from jax.sharding import PartitionSpec as P

    return ((r".", P()),)


def fed_data_rules(axis: str = "clients") -> Rules:
    """Stacked federated client data ({"x","y","mask"}: [N, S, ...]):
    leading client axis sharded over the federated-parallel mesh axis.
    Consumed by `parallel/round.shard_fed_data`."""
    from jax.sharding import PartitionSpec as P

    return ((r"^(x|y|mask)$", P(axis)),)


def paged_kv_cache_spec(axis: str = "mp"):
    """PartitionSpec for the DecodeEngine's persistent KV page pool
    `[L, n_pages, page_size, H, Dh]` (serving/engine.py): heads sharded
    over `axis`, page axes replicated — the decode-side continuation of
    the column-split attention projections (each chip holds the K/V of
    its own heads; no cross-chip traffic inside attention, one all-reduce
    at the wo row-matmul). The int32 page table `[S, max_pages]` rides the
    carry replicated (it is indexed identically on every chip)."""
    from jax.sharding import PartitionSpec as P

    return P(None, None, None, axis, None)


def paged_kv_scale_spec(axis: str = "mp"):
    """PartitionSpec for the int8 paged pool's per-(page, head) scales
    `[L, n_pages, H]` (`kv_quant: int8`): heads sharded over `axis` like
    the pool rows they dequantize, page axis replicated — a scale leaf
    landing on the wrong chip would force a gather in front of every
    in-place dequant."""
    from jax.sharding import PartitionSpec as P

    return P(None, None, axis)


def paged_latent_cache_spec(axis: str = "mp", size: int = 1):
    """PartitionSpec for both leaves of a LATENT page pool (latents
    `[L, n_pages, page_size, width]`, indexer keys `[L, n_pages, page_size,
    index_dim]`: llm/decode.py make_paged_latent_decode). A token's row is
    shared by every head, so there is no heads axis for `axis` to split:
    the pool replicates, and an `axis` of more than one chip is refused by
    the mechanism lacking rather than served from a pool that every chip
    would hold whole."""
    from jax.sharding import PartitionSpec as P

    if size > 1:
        raise NotImplementedError(
            f"{axis}={size} over latent pages: the page pool shards its "
            "heads axis (paged_kv_cache_spec) and a latent row has none; "
            "splitting the up-projections' heads over chips that each hold "
            "the whole pool is not built")
    return P()


TABLES = {
    "transformer_lm": transformer_lm_rules,
    "mlp_cnn": mlp_cnn_rules,
    "lora": lora_rules,
}


def rules_for(name: str, axis: str = "mp") -> Rules:
    """Look a named rule table up (the `device_args.partition_rules`
    values config.py validates)."""
    try:
        return TABLES[name](axis)
    except KeyError:
        raise PartitionRuleError(
            f"unknown partition rule table {name!r}; "
            f"valid: {sorted(TABLES)}") from None


def table_for_model(model) -> str:
    """Default table for a model instance: the flagship TransformerLM maps
    to its Megatron table, everything else to the Dense/Conv table."""
    return ("transformer_lm"
            if type(model).__name__ == "TransformerLM" else "mlp_cnn")


def resolve(rules, params: Pytree, *, axis: str = "mp",
            on_unmatched: str = ERROR) -> Pytree:
    """`match_partition_rules` accepting a table NAME or a rule sequence —
    the one entry point train (round programs, CentralizedTrainer) and
    serve (DecodeEngine) both call, so their resolved tables cannot
    drift."""
    if isinstance(rules, str):
        rules = rules_for(rules, axis)
    return match_partition_rules(rules, params, on_unmatched=on_unmatched)


def shard_params(params: Pytree, mesh, rules="transformer_lm", *,
                 axis: str = "mp", on_unmatched: str = ERROR,
                 specs: Optional[Pytree] = None) -> Pytree:
    """device_put the params with registry-resolved NamedShardings over
    `mesh`. Pass `specs` to reuse an already-resolved tree (e.g. for a
    spec table the caller also asserts on)."""
    import jax
    from jax.sharding import NamedSharding

    if axis not in mesh.axis_names:
        raise PartitionRuleError(
            f"mesh axes {mesh.axis_names} have no {axis!r} axis; partition "
            f"rules shard over {axis!r} — add it to the mesh shape")
    if specs is None:
        specs = resolve(rules, params, axis=axis, on_unmatched=on_unmatched)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs)
