"""Round-block execution (parallel/round.py build_block_fn + the simulator's
pipelined blocked driver): K federated rounds scanned inside ONE XLA program
must be indistinguishable — history, final params, client_states, DP epsilon —
from K per-round dispatches, and the block program must compile exactly once
across a multi-block run (a retrace per block would pay back the dispatch
savings with interest)."""
import jax
import numpy as np

import fedml_tpu
from fedml_tpu.simulation.simulator import Simulator


def _cfg(backend="sp", **train_overrides):
    d = {
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "data_args": {"dataset": "synthetic", "partition_method": "hetero",
                      "partition_alpha": 0.5,
                      "extra": {"synthetic_samples_per_client": 32}},
        "model_args": {"model": "lr"},
        "train_args": {
            "federated_optimizer": "FedAvg",
            "client_num_in_total": 8,
            "client_num_per_round": 4,
            "comm_round": 12,
            "epochs": 1,
            "batch_size": 8,
            "learning_rate": 0.1,
            **train_overrides,
        },
        "validation_args": {"frequency_of_the_test": 0},
        "comm_args": {"backend": backend},
    }
    return fedml_tpu.init(config=d)


def _assert_histories_match(h_ref, h_blk):
    assert len(h_ref) == len(h_blk)
    for a, b in zip(h_ref, h_blk):
        assert set(a) == set(b), f"row keys differ: {set(a)} vs {set(b)}"
        assert a["round"] == b["round"]
        for k in a:
            np.testing.assert_allclose(
                a[k], b[k], rtol=2e-5, atol=1e-6,
                err_msg=f"history[{a['round']}][{k}] diverged")


def _assert_trees_match(t_ref, t_blk, rtol=2e-5, atol=1e-6):
    ref, blk = jax.device_get(t_ref), jax.device_get(t_blk)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(blk)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


def _run_pair(backend="sp", rounds_per_block=4, **overrides):
    """Run the identical workload per-round and blocked; return both sims."""
    ref = Simulator(_cfg(backend=backend, **overrides))
    ref.run()
    blk = Simulator(_cfg(backend=backend, **overrides,
                         extra={"rounds_per_block": rounds_per_block,
                                **overrides.get("extra", {})}))
    blk.run()
    return ref, blk


def test_k4_block_matches_per_round_sp():
    """K=4 on the single-device path: bit-compatible history + final state."""
    ref, blk = _run_pair(backend="sp", rounds_per_block=4)
    assert blk.block_fn is not None, "blocked run never used the block fn"
    _assert_histories_match(ref.history, blk.history)
    _assert_trees_match(ref.server_state.params, blk.server_state.params)
    _assert_trees_match(ref.client_states, blk.client_states)


def test_k4_block_matches_per_round_xla_padded_with_eval_cadence():
    """The hard case: 8-device mesh with pad rounds (5 sampled clients pad to
    8), stateful clients (SCAFFOLD control variates scatter back through the
    scan), and an eval cadence (6) that K=4 does not divide — so the run
    mixes full blocks with per-round ragged pieces around eval barriers."""
    over = dict(federated_optimizer="SCAFFOLD",
                client_num_in_total=12, client_num_per_round=5)
    ref = Simulator(_cfg(backend="xla", **over))
    assert ref.mesh is not None and ref.mesh.devices.size == 8
    ref.cfg.validation_args.frequency_of_the_test = 6
    ref.run()
    cfg_b = _cfg(backend="xla", extra={"rounds_per_block": 4}, **over)
    cfg_b.validation_args.frequency_of_the_test = 6
    blk = Simulator(cfg_b)
    blk.run()
    assert blk.block_fn is not None, "blocked run never used the block fn"
    # eval rows land on the same rounds in both runs
    assert [r["round"] for r in ref.history if "test_acc" in r] == \
           [r["round"] for r in blk.history if "test_acc" in r]
    _assert_histories_match(ref.history, blk.history)
    _assert_trees_match(ref.server_state.params, blk.server_state.params)
    _assert_trees_match(ref.client_states, blk.client_states)


def test_block_dp_epsilon_matches_per_round():
    """The DP accountant advances once per round in blocked mode too: every
    history row's epsilon matches the per-round run at the same composition
    count, and the noise itself (rng-driven, inside the program) is
    identical."""
    dp = {"dp_args": {"enable_dp": True, "dp_solution_type": "ldp",
                      "epsilon": 0.9, "delta": 1e-5, "clipping_norm": 1.0}}
    ref = Simulator(fedml_tpu.init(config={**_raw(), **dp}))
    ref.run()
    raw_b = _raw()
    raw_b["train_args"]["extra"] = {"rounds_per_block": 4}
    blk = Simulator(fedml_tpu.init(config={**raw_b, **dp}))
    blk.run()
    assert all("dp_epsilon" in r for r in blk.history)
    _assert_histories_match(ref.history, blk.history)
    _assert_trees_match(ref.server_state.params, blk.server_state.params)


def _raw():
    return {
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "data_args": {"dataset": "synthetic",
                      "extra": {"synthetic_samples_per_client": 32}},
        "model_args": {"model": "lr"},
        "train_args": {
            "federated_optimizer": "FedAvg",
            "client_num_in_total": 8, "client_num_per_round": 8,
            "comm_round": 8, "epochs": 1, "batch_size": 8,
            "learning_rate": 0.1,
        },
        "validation_args": {"frequency_of_the_test": 0},
        "comm_args": {"backend": "sp"},
    }


def test_health_stats_do_not_change_training():
    """The in-jit per-client health stats (ISSUE 3) are observation-only:
    a run with health_stats=False produces EXACTLY (rtol=0) the history and
    final params of the default-on run — the health arrays are extra
    outputs, never inputs."""
    on = Simulator(_cfg())             # health_stats defaults to on
    on.run()
    off = Simulator(_cfg(extra={"health_stats": False}))
    off.run()
    assert len(on.history) == len(off.history)
    for a, b in zip(on.history, off.history):
        assert a == b, f"history diverged at round {a['round']}"
    _assert_trees_match(on.server_state.params, off.server_state.params,
                        rtol=0, atol=0)


def test_health_block_equivalence_and_single_transfer_shape():
    """Acceptance pin (ISSUE 3): with health enabled (the default), blocked
    K=4 and per-round runs still produce identical history/params/
    client_states — the existing equivalence suite runs health-on already;
    this pin additionally checks the health arrays themselves ride the
    metrics transfer with the right shape and sane values in BOTH engines,
    on the 8-device mesh with pad rounds (5 sampled -> 8 slots)."""
    import jax.numpy as jnp

    over = dict(client_num_in_total=12, client_num_per_round=5)
    ref, blk = _run_pair(backend="xla", rounds_per_block=4, **over)
    _assert_histories_match(ref.history, blk.history)
    _assert_trees_match(ref.server_state.params, blk.server_state.params)
    # both trackers saw every round
    assert ref.health is not None and blk.health is not None
    assert ref.health.rounds_seen == blk.health.rounds_seen == 12
    # the health arrays really are per-slot [m] outputs of the jitted round
    ids, weights = ref._pad_ids(ref.sample_clients(0))
    out = ref.round_fn(
        ref.server_state, ref.client_states, ref.data,
        jnp.asarray(ids), jnp.asarray(weights),
        jax.random.fold_in(jax.random.key(0), 99), ref.hook_state)
    h = jax.device_get(out.metrics["health"])
    assert set(h) == {"update_norm", "cosine", "loss_delta"}
    for v in h.values():
        assert v.shape == (len(ids),)
    assert np.all(h["update_norm"] >= 0)
    assert np.all(np.abs(h["cosine"]) <= 1.0 + 1e-5)


def test_k1_uses_per_round_driver():
    """rounds_per_block=1 must reduce to today's behavior exactly: the
    blocked driver is never entered and the block fn is never built."""
    cfg = _cfg(extra={"rounds_per_block": 1})
    sim = Simulator(cfg)
    sim.run()
    assert sim.block_fn is None
    ref = Simulator(_cfg())
    ref.run()
    _assert_histories_match(ref.history, sim.history)


def test_block_knobs_validated_at_config_load():
    """A typo'd rounds_per_block fails at init, not as a shape error K
    rounds into a run."""
    import pytest

    for bad in (0, -3, 2.5, "eight"):
        with pytest.raises(ValueError, match="rounds_per_block"):
            _cfg(extra={"rounds_per_block": bad})
    with pytest.raises(ValueError, match="block_pipeline_depth"):
        _cfg(extra={"block_pipeline_depth": 0})
    _cfg(extra={"rounds_per_block": 8, "block_pipeline_depth": 3})  # ok


def test_block_fn_compiles_once_across_blocks():
    """Retrace guard: a 12-round K=4 run is 3 block dispatches of ONE
    compiled program. Re-running the warm simulator (same shapes, stacked
    [K, m] schedule rebuilt from fresh numpy arrays each block) must leave
    the block jit's compile cache at ONE entry — any shape- or
    weak-type-driven retrace would add a second."""
    sim = Simulator(_cfg(extra={"rounds_per_block": 4}))
    sim.run()              # cold run: compiles the block program once
    assert sim.block_fn._cache_size() == 1
    sim.run()              # 3 more K=4 blocks through the warm caches
    assert sim.block_fn._cache_size() == 1, (
        f"block fn retraced: {sim.block_fn._cache_size()} compiled entries "
        "after a warm multi-block run (expected 1)")
