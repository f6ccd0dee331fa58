"""The benchmark's operation and byte counts, against hand arithmetic."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import peaks  # noqa: E402
import work  # noqa: E402

SMOLLM = {"hidden_size": 576, "intermediate_size": 1536,
          "num_attention_heads": 9, "num_key_value_heads": 3, "head_dim": 64,
          "num_hidden_layers": 30, "vocab_size": 49152,
          "tie_word_embeddings": True}


def test_smollm_param_count():
    assert work.lm_param_count(SMOLLM) == 134_515_008


def test_smollm_flops_per_token():
    # matmul weights: 30 x (884,736 attention + 2,654,208 MLP) + the tied
    # 49,152 x 576 head; attention scores and values: 2 x 2 x 256 x 576 a
    # layer; training is three times the forward
    matmul = 30 * (884_736 + 2_654_208) + 49_152 * 576
    attn = 30 * 4 * 256 * 576
    assert work.lm_train_flops_per_token(SMOLLM, 256) == 3 * (2 * matmul + attn)


@pytest.mark.parametrize("k,u,p,flops,nbytes", [
    (1, 3, 134_515_008, 807_090_048.0, 2_152_240_128.0),
    (16, 128, 6922, 28_352_512.0, 3_987_072.0),
])
def test_eq4_panel_work(k, u, p, flops, nbytes):
    assert work.eq4_panel_work(k, u, p) == (flops, nbytes)


def test_roofline_picks_the_binding_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = work.roofline_s(*work.eq4_panel_work(1, 3, 134_515_008), pk)
    assert bound == "memory" and t == pytest.approx(2_152_240_128 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_lm_config_file_matches_the_count():
    path = HERE / "configs" / "smollm-135m-fleet4.json"
    if not path.exists():
        pytest.skip("no smollm configuration in this benchmark")
    cfg = json.loads(path.read_text())
    assert work.lm_param_count(cfg["model"]) == 134_515_008
