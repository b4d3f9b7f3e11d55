"""The work functions against counts made by hand for one small shape each."""
import types

import pytest

from chipbench import manifest, work


def cell(model, traffic):
    return types.SimpleNamespace(config={"model": model}, traffic=traffic)


def test_resnet_forward_by_hand():
    # 4x4 image, 3 channels, stem 8, one block a stage, two stages, 10 classes
    m = {"image_size": 4, "image_channels": 3, "stem_filters": 8,
         "stage_sizes": [1, 1], "num_classes": 10}
    stem = 2 * 16 * 8 * 3 * 9                       # 6,912
    stage1 = 2 * (2 * 16 * 8 * 8 * 9)               # two 3x3 8->8 at 4x4
    stage2 = (2 * 4 * 16 * 8 * 9 + 2 * 4 * 16 * 16 * 9   # 8->16 s2, 16->16
              + 2 * 4 * 16 * 8 * 1)                 # the 1x1 shortcut
    head = 2 * 16 * 10
    fwd, got_stem = work.resnet_forward_flops(m)
    assert got_stem == stem
    assert fwd == stem + stage1 + stage2 + head
    got = work.resnet_train_flops(cell(m, {}), {"samples": 5})
    assert got["flops"] == (3 * fwd - stem) * 5


def test_resnet18_cifar_is_the_known_half_gigamac():
    cfg = manifest.load_json(
        manifest.HERE / "configs" / "resnet18gn_cifar10.json")
    fwd, _ = work.resnet_forward_flops(cfg["model"])
    assert 1.10e9 < fwd < 1.12e9        # 0.556 GMACs, the published count


LM = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
      "num_attention_heads": 2, "vocab_size": 32}


def test_lm_counts_by_hand():
    assert work.lm_matmul_params(LM) == 2 * (4 * 64 + 3 * 8 * 16) + 8 * 32
    assert work.causal_attention_flops(LM, 4) == 2 * 2.0 * 16 * 8
    tr = {"seq_len": 4, "lora_rank": 2, "lora_targets": ["wq", "wo"],
          "batch_size": 3}
    per_seq = (4 * 1536 * 4 + 3 * 512.0
               + 2 * 2 * 3 * 2 * (8 * 2 + 2 * 8) * 4)
    got = work.lora_train_flops(cell(LM, tr), {"tokens": 8})
    assert got["flops"] == per_seq * 2
    assert work.decode_flops(cell(LM, tr), {"processed_tokens": 10})[
        "flops"] == 2 * 1536 * 10


def test_flash_and_paged_by_hand():
    tr = {"seq_len": 4, "batch_size": 3}
    c = cell(LM, tr)            # b 3, t 4, heads 2 of 4
    assert work.flash_fwd_call(c, {}) == {
        "flops": 2.0 * 3 * 2 * 16 * 4, "bytes": 4.0 * 3 * 4 * 2 * 4 * 2}
    assert work.flash_bwd_call(c, {})["flops"] == 5.0 * 3 * 2 * 16 * 4
    got = work.paged_attention_traffic(c, {"context_token_sum": 100})
    # K and V of 8 wide, 2 layers, 2 bytes, for 100 context tokens
    assert got["bytes"] == 100 * 2 * 8 * 2 * 2
    assert got["flops"] == 100 * 2 * 2 * 8 * 2


@pytest.mark.parametrize("name", manifest.names("work"))
def test_every_work_function_returns_flops_and_bytes(name):
    tr = {"seq_len": 4, "lora_rank": 2, "lora_targets": ["wq"],
          "batch_size": 1}
    m = {**LM, "image_size": 4, "image_channels": 3, "stem_filters": 8,
         "stage_sizes": [1], "num_classes": 2}
    log = {"samples": 1, "tokens": 4, "context_token_sum": 1,
           "processed_tokens": 1}
    got = manifest.find("work", name)(cell(m, tr), log)
    assert set(got) == {"flops", "bytes"} and got["flops"] > 0
