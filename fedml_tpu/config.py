"""Typed configuration tree.

TPU-native replacement for the reference's untyped Arguments attr-bag
(reference: python/fedml/arguments.py:75-199, where every consumer probes
`hasattr(args, ...)`). We keep the same YAML section names
(common_args/data_args/model_args/train_args/validation_args/device_args/
comm_args/tracking_args — reference canonical instance
examples/federate/quick_start/parrot/fedml_config.yaml:1-43) so reference
configs load unchanged, but validate into dataclasses at load time.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

import yaml

# Training types (reference: python/fedml/constants.py:2-26)
TRAINING_TYPE_SIMULATION = "simulation"
TRAINING_TYPE_CROSS_SILO = "cross_silo"
TRAINING_TYPE_CROSS_DEVICE = "cross_device"
TRAINING_TYPE_CROSS_CLOUD = "cross_cloud"
TRAINING_TYPE_CENTRALIZED = "centralized"  # non-federated baseline runner

# Simulation backends. The reference offers sp/MPI/NCCL; the TPU-native
# backend is "xla": the whole round is one XLA program over a device mesh.
BACKEND_SP = "sp"
BACKEND_XLA = "xla"

SCENARIO_HORIZONTAL = "horizontal"
SCENARIO_HIERARCHICAL = "hierarchical"


def _apply(dc, d: dict):
    """Fill dataclass fields from a dict; unknown keys go to .extra."""
    names = {f.name for f in dataclasses.fields(dc)}
    for k, v in d.items():
        if k in names:
            setattr(dc, k, v)
        else:
            dc.extra[k] = v
    return dc


@dataclass
class CommonArgs:
    training_type: str = TRAINING_TYPE_SIMULATION
    random_seed: int = 0
    scenario: str = SCENARIO_HORIZONTAL
    config_version: str = "release"
    extra: dict = field(default_factory=dict)


@dataclass
class DataArgs:
    dataset: str = "synthetic"
    data_cache_dir: str = "~/fedml_data"
    partition_method: str = "hetero"   # hetero = Dirichlet non-IID, homo = IID
    partition_alpha: float = 0.5
    extra: dict = field(default_factory=dict)


@dataclass
class ModelArgs:
    model: str = "lr"
    extra: dict = field(default_factory=dict)


@dataclass
class TrainArgs:
    federated_optimizer: str = "FedAvg"
    client_id_list: Any = "[]"
    client_num_in_total: int = 2
    client_num_per_round: int = 2
    comm_round: int = 10
    epochs: int = 1
    batch_size: int = 10
    client_optimizer: str = "sgd"
    learning_rate: float = 0.03
    momentum: float = 0.0
    weight_decay: float = 0.0
    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.0
    # Mixed-precision compute: "float32" or "bfloat16". bf16 keeps params and
    # optimizer accumulation in f32 but runs matmuls/convs on the MXU in bf16
    # (the reference has no equivalent — torch AMP is never used in its FL loops).
    compute_dtype: str = "float32"
    # FedProx / FedDyn / Mime hyper-params (explicit zeros are honored)
    fedprox_mu: float = 0.01
    feddyn_alpha: float = 0.01
    mime_beta: float = 0.9
    extra: dict = field(default_factory=dict)


@dataclass
class ValidationArgs:
    frequency_of_the_test: int = 1
    extra: dict = field(default_factory=dict)


@dataclass
class DeviceArgs:
    using_gpu: bool = False          # kept for reference-YAML compat; ignored on TPU
    gpu_id: int = 0
    mesh_shape: Optional[dict] = None  # e.g. {"clients": 8} or {"silos": 2, "intra": 4}
    extra: dict = field(default_factory=dict)


@dataclass
class CommArgs:
    backend: str = BACKEND_XLA
    grpc_ipconfig_path: str = ""
    extra: dict = field(default_factory=dict)


@dataclass
class TrackingArgs:
    enable_tracking: bool = False
    enable_wandb: bool = False
    log_file_dir: str = "./log"
    run_name: str = "fedml_tpu_run"
    extra: dict = field(default_factory=dict)


@dataclass
class SecurityArgs:
    """Attack/defense plugin config (reference: core/security/fedml_attacker.py:29,
    fedml_defender.py:55 read enable_attack/enable_defense + *_spec)."""
    enable_attack: bool = False
    attack_type: str = ""
    attack_spec: dict = field(default_factory=dict)
    enable_defense: bool = False
    defense_type: str = ""
    defense_spec: dict = field(default_factory=dict)
    extra: dict = field(default_factory=dict)


@dataclass
class DPArgs:
    """Differential privacy (reference: core/dp/fedml_differential_privacy.py:13)."""
    enable_dp: bool = False
    mechanism_type: str = "gaussian"   # gaussian | laplace
    dp_solution_type: str = "ldp"      # ldp (client noise) | cdp (server clip+noise)
    epsilon: float = 1.0
    delta: float = 1e-5
    sensitivity: float = 1.0
    clipping_norm: float = 1.0
    extra: dict = field(default_factory=dict)


@dataclass
class ServeArgs:
    """Model-serving knobs (serving/). All engine knobs ride `extra` so
    reference YAMLs (which have no serving section) load unchanged.
    The authoritative key set, kinds/bounds, and gating live in
    serving/knobs.py (KNOBS) — validation iterates that registry, and
    graftlint's knob-drift rule cross-checks it against the predictor
    and fleet mappings, so this docstring is prose, not a key list:
      decode_slots      — >0 starts the continuous-batching DecodeEngine
                          (serving/engine.py) with that many slots over
                          one pool of KV pages; every knob below but
                          engine_max_len, sampler_cache_size and
                          drain_timeout_s needs it
      kv_page_size      — KV rows a page (default 16); kv_n_pages sizes
                          the pool, prefill_chunk the admission chunk,
                          prefix_cache reuses identical prompt prefixes
      engine_max_len    — per-slot KV capacity (prompt + max_new <= this)
      engine_eos_id     — token id that retires a slot early (omit: none)
      engine_fetch_chunk — device frames kept in flight before the host
                          fetches (dispatch-ahead depth)
      sampler_cache_size — LRU cap on per-top_k compiled samplers
      engine_mp          — >1 runs the engine tensor-parallel over an
                          {"mp": N} mesh (weights + persistent KV pool
                          sharded via the parallel/partition.py registry)
    Decode-speed knobs (ISSUE 11 — both need the engine, decode_slots):
      paged_kernel      — fused Pallas paged-attention decode kernel
                          (ops/paged_attention.py): pages read in place,
                          no gather copy, and only the pages live slots
                          hold are walked
      spec_decode       — "ngram" turns on greedy-exact self-drafted
                          speculative decoding ("off" default)
      spec_k            — draft tokens per speculative window (needs
                          spec_decode: ngram)
    Fleet knobs (ISSUE 9 — serving/scheduler.py consumes them through
    scheduler.fleet_knobs; drain_timeout_s rides the predictor mapping):
      drain_timeout_s      — bound on stop(drain=True): how long in-flight
                             decodes get to finish at scale-down
      shed_watermark       — >0 arms gateway load shedding: above
                             watermark × ready_replicas in-flight, new
                             requests get 429 + Retry-After
      retry_after_s        — the Retry-After hint on sheds
      probation_deadline_s — how long a SUSPECT replica gets to answer
                             /ready again before it is declared DEAD
      probe_backoff_s      — initial probation re-probe interval
                             (exponential, capped at 1s)"""
    extra: dict = field(default_factory=dict)


@dataclass
class Config:
    common_args: CommonArgs = field(default_factory=CommonArgs)
    data_args: DataArgs = field(default_factory=DataArgs)
    model_args: ModelArgs = field(default_factory=ModelArgs)
    train_args: TrainArgs = field(default_factory=TrainArgs)
    validation_args: ValidationArgs = field(default_factory=ValidationArgs)
    device_args: DeviceArgs = field(default_factory=DeviceArgs)
    comm_args: CommArgs = field(default_factory=CommArgs)
    tracking_args: TrackingArgs = field(default_factory=TrackingArgs)
    security_args: SecurityArgs = field(default_factory=SecurityArgs)
    dp_args: DPArgs = field(default_factory=DPArgs)
    serve_args: ServeArgs = field(default_factory=ServeArgs)
    # role assignment for cross-silo runs (reference: arguments.py --rank/--role)
    rank: int = 0
    role: str = "server"
    run_id: str = "0"
    # per-client override config (reference: __init__.py:188-214
    # _update_client_specific_args — a `client_specific_args` YAML section
    # whose `data_silo_config` lists one override YAML per client rank;
    # rank r>0 merges file [r-1] over its base config)
    client_specific_args: dict = field(default_factory=dict)

    SECTION_TYPES = {
        "common_args": CommonArgs,
        "data_args": DataArgs,
        "model_args": ModelArgs,
        "train_args": TrainArgs,
        "validation_args": ValidationArgs,
        "device_args": DeviceArgs,
        "comm_args": CommArgs,
        "tracking_args": TrackingArgs,
        "security_args": SecurityArgs,
        "dp_args": DPArgs,
        "serve_args": ServeArgs,
    }

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        cfg = cls()
        # "serve" is accepted as an alias for "serve_args" (the serving
        # docs/specs use the short name; every other section is *_args).
        # Both present is ambiguous — refusing beats silently dropping one
        # (a merged-YAML pipeline losing decode_slots would bring the
        # replica up in per-request mode with no signal)
        if "serve" in d and isinstance(d["serve"], dict):
            if "serve_args" in d:
                raise ValueError(
                    "config has both 'serve' and 'serve_args' sections — "
                    "'serve' is an alias for 'serve_args'; keep one")
            d = {**d, "serve_args": d["serve"]}
        for section, typ in cls.SECTION_TYPES.items():
            if section in d and isinstance(d[section], dict):
                _apply(getattr(cfg, section), d[section])
        for k in ("rank", "role", "run_id"):
            if k in d:
                setattr(cfg, k, d[k])
        if isinstance(d.get("client_specific_args"), dict):
            cfg.client_specific_args = dict(d["client_specific_args"])
        cfg.validate()
        return cfg

    @classmethod
    def from_yaml(cls, path: str | Path) -> "Config":
        with open(Path(path).expanduser()) as f:
            return cls.from_dict(yaml.safe_load(f) or {})

    def to_dict(self) -> dict:
        out = {}
        for section in self.SECTION_TYPES:
            sec = dataclasses.asdict(getattr(self, section))
            extra = sec.pop("extra", {})
            sec.update(extra)
            out[section] = sec
        out.update(rank=self.rank, role=self.role, run_id=self.run_id)
        return out

    def merge_overrides(self, d: dict) -> None:
        """Merge a (possibly partial) config dict over this config: known
        section dicts merge into their sections. Flat keys (the reference's
        attr-bag style — arguments.py set_attr_from_config sets everything
        flat) route to whichever section declares that field (so a flat
        `data_cache_dir` reaches data_args, `model` reaches model_args);
        undeclared flat keys default to train_args.extra. Re-validates
        after the merge."""
        for k, v in d.items():
            if k in self.SECTION_TYPES and isinstance(v, dict):
                _apply(getattr(self, k), v)
            elif k in ("rank", "role", "run_id"):
                setattr(self, k, v)
            else:
                _apply(getattr(self, _FLAT_KEY_SECTION.get(k, "train_args")),
                       {k: v})
        self.validate()

    def apply_data_silo_config(self, base_dir: Optional[Path] = None) -> None:
        """Per-client config overrides (reference: python/fedml/__init__.py
        :188-214 `_update_client_specific_args`): when
        `client_specific_args.data_silo_config` lists override YAMLs and this
        config's rank is a client rank (>0), merge file [rank-1] over the
        base config. Paths resolve against `base_dir` (the main config
        file's directory) first, then cwd."""
        silo_cfgs = (self.client_specific_args.get("data_silo_config")
                     or self.train_args.extra.get("data_silo_config"))
        if not silo_cfgs or self.rank <= 0:
            return
        if self.rank > len(silo_cfgs):
            raise ValueError(
                f"rank {self.rank} has no data_silo_config entry "
                f"({len(silo_cfgs)} files listed)")
        p = Path(str(silo_cfgs[self.rank - 1])).expanduser()
        if not p.is_absolute() and base_dir is not None \
                and (Path(base_dir) / p).exists():
            p = Path(base_dir) / p
        with open(p) as f:
            self.merge_overrides(yaml.safe_load(f) or {})

    def validate(self) -> None:
        t = self.train_args
        if t.client_num_per_round > t.client_num_in_total:
            raise ValueError(
                f"client_num_per_round ({t.client_num_per_round}) > "
                f"client_num_in_total ({t.client_num_in_total})"
            )
        if t.comm_round < 1 or t.epochs < 1 or t.batch_size < 1:
            raise ValueError("comm_round, epochs and batch_size must be >= 1")
        # round-block execution knobs (simulation/simulator.py): K rounds
        # scanned inside one XLA program, a bounded number of blocks in
        # flight. Validated here so a typo'd YAML fails at load, not as a
        # shape error K rounds into a run.
        for knob, lo in (("rounds_per_block", 1), ("block_pipeline_depth", 1)):
            val = t.extra.get(knob)
            if val is None:
                continue
            try:
                ok = int(val) >= lo and int(val) == float(val)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"train_args.{knob} must be an integer >= {lo}; "
                    f"got {val!r}")
        # Parrot-scale simulation knobs (ISSUE 8): cohort_chunk streams an
        # m-client round through HBM-bounded chunks (simulation/simulator.py
        # chunked driver), ingest_prefetch sizes the double-buffered
        # host->device pipeline (simulation/ingest.py), cost_model switches
        # LPT costs to fitted runtimes (schedule.CostModel). Validated here
        # so a typo'd YAML fails at load, not chunks into a run.
        for knob, lo in (("cohort_chunk", 1), ("ingest_prefetch", 0)):
            val = t.extra.get(knob)
            if val is None:
                continue
            try:
                ok = (not isinstance(val, bool)
                      and int(val) == float(val) and int(val) >= lo)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"train_args.{knob} must be an integer >= {lo}; "
                    f"got {val!r}")
        # ingest_prefetch only takes effect inside the chunked driver —
        # without cohort_chunk it would be silently ignored; refuse at load
        # (same gating discipline as the paged-KV serve knobs)
        if t.extra.get("ingest_prefetch") is not None \
                and not t.extra.get("cohort_chunk"):
            raise ValueError(
                "train_args.ingest_prefetch requires cohort_chunk — the "
                "streaming ingest pipeline only exists for chunked rounds; "
                "without it the knob would be silently ignored")
        cm = t.extra.get("cost_model")
        if cm not in (None, False, True):
            if not isinstance(cm, dict):
                raise ValueError(
                    "train_args.cost_model must be a boolean or a dict of "
                    f"{{fit_after_rounds, error_threshold}}; got {cm!r}")
            unknown_cm = set(cm) - {"fit_after_rounds", "error_threshold"}
            if unknown_cm:
                raise ValueError(
                    f"unknown cost_model knob(s) {sorted(unknown_cm)}; "
                    "valid: ['error_threshold', 'fit_after_rounds']")
            far = cm.get("fit_after_rounds")
            if far is not None and (isinstance(far, bool)
                                    or not isinstance(far, int) or far < 1):
                raise ValueError(
                    "cost_model.fit_after_rounds must be an integer >= 1; "
                    f"got {far!r}")
            et = cm.get("error_threshold")
            if et is not None:
                try:
                    ok = not isinstance(et, bool) and float(et) > 0
                except (TypeError, ValueError):
                    ok = False
                if not ok:
                    raise ValueError(
                        "cost_model.error_threshold must be a positive "
                        f"number; got {et!r}")
        # in-jit health stats cannot ride chunked rounds (the cosine stat
        # needs the full update stack — parallel/round.build_chunk_fns);
        # an EXPLICIT health_stats=true alongside cohort_chunk is refused
        # here, while the default-on value silently degrades in the
        # simulator (documented in README "Scale-out simulation")
        if t.extra.get("cohort_chunk") and t.extra.get("health_stats") is True:
            raise ValueError(
                "train_args.health_stats=true cannot be combined with "
                "cohort_chunk: per-client health stats need the full "
                "update stack the chunked engine exists to avoid "
                "materializing")
        # cross-silo durability knobs (ISSUE 10): server checkpoint/resume,
        # client silence watchdog + heartbeats, liveness eviction, bounded
        # quorum re-arms. Validated here so a typo'd YAML fails at load,
        # not as a hang N rounds into a federation.
        for knob in ("round_timeout", "heartbeat_s", "liveness_timeout_s",
                     "server_timeout_s"):
            val = t.extra.get(knob)
            if val is None:
                continue
            try:
                ok = not isinstance(val, bool) and float(val) > 0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"train_args.{knob} must be a positive number of "
                    f"seconds; got {val!r}")
        qf = t.extra.get("quorum_frac")
        if qf is not None:
            try:
                ok = not isinstance(qf, bool) and 0.0 < float(qf) <= 1.0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    "train_args.quorum_frac must be a fraction in (0, 1]; "
                    f"got {qf!r}")
        for knob, lo in (("max_rearms", 1), ("checkpoint_every", 0),
                         ("checkpoint_keep", 1)):
            val = t.extra.get(knob)
            if val is None:
                continue
            try:
                ok = (not isinstance(val, bool)
                      and int(val) == float(val) and int(val) >= lo)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"train_args.{knob} must be an integer >= {lo}; "
                    f"got {val!r}")
        for knob in ("resume", "reattach"):
            val = t.extra.get(knob)
            if val is not None and not isinstance(val, bool):
                raise ValueError(
                    f"train_args.{knob} must be a boolean; got {val!r}")
        # resume without a checkpoint_dir would be silently ignored (there
        # is nothing to resume FROM) — refuse at load, same gating
        # discipline as the paged-KV serve knobs
        if t.extra.get("resume") and not t.extra.get("checkpoint_dir"):
            raise ValueError(
                "train_args.resume requires checkpoint_dir — resume loads "
                "the latest checkpoint under it; without one the knob "
                "would be silently ignored")
        # run-health export plane (utils/prometheus.py): /metrics endpoint
        # port. Validated at load so a typo'd YAML fails before a run
        # silently comes up unscrapeable.
        mp = self.common_args.extra.get("metrics_port")
        if mp is not None:
            try:
                # bool is an int subtype: `metrics_port: true` would
                # otherwise pass as port 1 and fail only at bind time
                ok = (not isinstance(mp, bool)
                      and int(mp) == float(mp) and 0 <= int(mp) <= 65535)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    "common_args.extra.metrics_port must be an integer in "
                    f"[0, 65535] (0 = ephemeral); got {mp!r}")
        # serving knobs (serving/engine.py and the fleet tier), validated
        # at load so a typo'd YAML fails before a replica silently comes up
        # in per-request mode (decode_slots=0 IS the per-request path).
        # The key set, kinds, and gating all live in serving/knobs.py —
        # THE serve-knob registry the predictor/fleet mappings and
        # graftlint's knob-drift rule also read, so the validated set and
        # the consumed set physically cannot drift (ISSUE 13). The import
        # is jax-free: serving/__init__ is lazy and knobs.py is a literal
        # table.
        from .serving.knobs import validate_serve_args

        validate_serve_args(self.serve_args.extra)
        # partitioning-plane knobs (parallel/partition.py): the rule-table
        # name must exist in the registry and the unmatched policy must be
        # a known one — a typo'd table fails at load, not as an
        # UnmatchedParamError mid-init. The lazy import keeps config load
        # jax-free (partition.py defers its own jax imports the same way).
        pr = self.device_args.extra.get("partition_rules")
        if pr is not None:
            from .parallel.partition import TABLES

            if pr not in TABLES:
                raise ValueError(
                    f"device_args.partition_rules must be one of "
                    f"{sorted(TABLES)}; got {pr!r}")
        um = self.device_args.extra.get("unmatched_params")
        if um is not None and um not in ("error", "replicated"):
            raise ValueError(
                "device_args.unmatched_params must be 'error' or "
                f"'replicated'; got {um!r}")
        # chaos plane + reliable delivery knobs (ISSUE 4): both specs are
        # parsed by their owning modules so validation never drifts from the
        # consumer; lazy imports keep config load jax-free and cycle-free.
        chaos = self.common_args.extra.get("chaos")
        if chaos is not None:
            from .comm.chaos import FaultSpec

            FaultSpec.from_dict(chaos)
        cr = self.common_args.extra.get("comm_retry")
        if cr not in (None, False):
            from .comm.reliable import RetryPolicy

            RetryPolicy.from_dict(cr)
        # live-loop soak knobs (ISSUE 15): `common_args.extra.soak` is
        # validated by its owning module against the SOAK_KNOBS registry
        # (pure literal; graftlint's knob-drift soak leg cross-checks the
        # soak_plan consumer) — unknown keys, bad kinds, and gated knobs
        # without their prerequisite all fail HERE, at load. The import
        # is jax-free by design (soak/__init__ is lazy, knobs.py is a
        # literal table).
        sk = self.common_args.extra.get("soak")
        if sk is not None:
            from .soak.knobs import validate_soak

            validate_soak(sk)
        # fleet-observability plane (ISSUE 18): `common_args.extra.obs_fleet`
        # (roster/port/cadence) validated by its owning module — a typo'd
        # roster or port fails at load, not as a fleet view that silently
        # never aggregates. Lazy import, jax-free by design.
        of = self.common_args.extra.get("obs_fleet")
        if of is not None:
            from .utils.obsfleet import validate_obs_fleet

            validate_obs_fleet(of)
        # wire codec plane (ISSUE 14): `comm_args.comm_codec` is validated
        # by its owning module against the CODEC_KNOBS registry (pure
        # literal, graftlint's knob-drift rule cross-checks the consumer) —
        # unknown keys, bad kinds, and knobs gated on an unselected codec
        # all fail HERE, at load. The import is jax-free by design.
        cc = self.comm_args.extra.get("comm_codec")
        if cc is not None:
            from .comm.codec import validate_comm_codec

            validate_comm_codec(cc)
            # secagg_premask_ratio only takes effect inside the secagg
            # client (quantize-then-mask); without secagg it would be
            # silently ignored — refuse at load (serve-knob discipline)
            if cc.get("secagg_premask_ratio") is not None \
                    and not t.extra.get("secagg"):
                raise ValueError(
                    "comm_codec.secagg_premask_ratio requires "
                    "train_args.secagg — the pre-mask sparsifier lives in "
                    "the secagg client; without it the knob would be "
                    "silently ignored")
        # DP on the cross-silo wire is wired into the PLAIN client only
        # (dp.make_upload_dp -> FedClientManager); the secagg client has no
        # noise stage, so enable_dp alongside secagg would silently upload
        # UN-NOISED masked updates while the operator believes DP is on —
        # refuse at load (same never-silently-ignored discipline)
        if self.common_args.training_type == TRAINING_TYPE_CROSS_SILO \
                and t.extra.get("secagg") and self.dp_args.enable_dp:
            raise ValueError(
                "dp_args.enable_dp cannot be combined with "
                "train_args.secagg: the secagg client has no client-side "
                "noise stage yet, so DP would be silently dropped — "
                "disable one (noise-before-mask is the composition a "
                "future PR can add behind this same check)")
        if self.common_args.training_type not in (
            TRAINING_TYPE_SIMULATION,
            TRAINING_TYPE_CROSS_SILO,
            TRAINING_TYPE_CROSS_DEVICE,
            TRAINING_TYPE_CROSS_CLOUD,
            TRAINING_TYPE_CENTRALIZED,
        ):
            raise ValueError(f"unknown training_type {self.common_args.training_type!r}")


# flat override key -> owning section, for reference-style flat silo
# overrides. train_args is listed LAST: later dict writes overwrite earlier
# ones, so its field names win any collision — which preserves the common
# case: batch_size/learning_rate/... are train knobs. (Reordering this
# tuple silently changes flat-key routing; test_config_silo pins it.)
_FLAT_KEY_SECTION: dict = {}
for _section in ("dp_args", "security_args", "tracking_args", "comm_args",
                 "device_args", "validation_args", "model_args", "data_args",
                 "common_args", "train_args"):
    for _f in dataclasses.fields(Config.SECTION_TYPES[_section]):
        if _f.name != "extra":
            _FLAT_KEY_SECTION[_f.name] = _section


def load_config(path: str | Path) -> Config:
    return Config.from_yaml(path)
