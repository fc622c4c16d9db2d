"""Operations and bytes from shapes, against counts worked by hand."""
import json
from pathlib import Path

import pytest

from bench import work
from bench.references import dense_decoder

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_causal_pairs_by_hand():
    # queries at 2, 3, 4 see 3 + 4 + 5 keys
    assert work.causal_pairs(2, 5) == 12
    assert work.causal_pairs(0, 4) == 1 + 2 + 3 + 4


def test_paged_decode_call_by_hand():
    # rows of 3 and 5 live positions, 4 query heads, 2 KV heads, head_dim 8:
    # 4 * 4 * 8 * (3 + 5) operations; K and V of 8 positions x 2 heads x 8
    # x 2 bytes, plus q in and o out for 2 rows x 4 heads x 8 x 2 bytes
    flops, nbytes = work.paged_decode_call([3, 5], hq=4, hkv=2, hd=8)
    assert flops == 1024
    assert nbytes == 2 * 2 * 8 * 8 * 2 + 2 * 2 * 4 * 8 * 2 == 768


def test_paged_prefill_call_by_hand():
    # row (0, 4): 10 pairs; row (4, 6): 4 + 5 + ... -> 5 + 6 = 11 pairs
    flops, nbytes = work.paged_prefill_call([(0, 4), (4, 6)], hq=2, hkv=1,
                                            hd=4)
    assert flops == 4 * 2 * 4 * 21
    # K/V of 4 then 6 positions (1 head x 4 x 2 bytes x 2), q + o of the
    # 4 then 2 queries (2 heads x 4 x 2 bytes x 2)
    assert nbytes == (64 + 128) + (96 + 64)


def test_min_seconds_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert work.min_seconds(1e12, 5e11, peak) == 5.0
    assert work.min_seconds(8e12, 1e11, peak) == 8.0


def test_model_flops_by_hand():
    f = work.ModelFlops({"L": 2, "hq": 2, "hkv": 1, "hd": 4, "blocks": 100,
                         "unembed": 30})
    # three prompt positions (6 pairs), the last one with logits
    assert f.prompt(0, 3, True) == 2 * 100 * 3 + 4 * 2 * 2 * 4 * 6 + 2 * 30
    assert f.prompt(0, 3, False) == 2 * 100 * 3 + 4 * 2 * 2 * 4 * 6
    assert f.decode(5) == 2 * 130 + 4 * 2 * 2 * 4 * 5


@pytest.mark.parametrize("name,blocks,unembed", [
    # 40 x (2048 x 48 x 64 + 2048 x 2048 + 3 x 2048 x 8192); 49155 x 2048
    ("granite-3-2b", 40 * 60_817_408, 100_669_440),
    # 16 x (4096 x 96 x 128 + 4096 x 4096 + 2 x 4096 x 16384); 50272 x 4096
    ("opt-6.7b", 16 * 201_326_592, 205_914_112),
])
def test_weights_per_token_of_each_configuration(name, blocks, unembed):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    c = dense_decoder.counts(cfg)
    assert (c["blocks"], c["unembed"]) == (blocks, unembed)


@pytest.mark.parametrize("name,eps,pos", [
    ("granite-3-2b", 1e-6, "rope"),
    ("opt-6.7b", 1e-6, "sinusoidal"),
])
def test_reference_runs_the_departures_not_the_published_values(name, eps,
                                                                 pos):
    """The file keeps each published value under its own key and the
    program's value under `departures`; the reference takes the latter."""
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    assert set(cfg["departures"]) <= set(cfg)
    assert all(cfg[k] != v for k, v in cfg["departures"].items())
    c = dict(dense_decoder.hyper(cfg))
    assert (c["eps"], c["pos"]) == (eps, pos)


def test_counts_match_the_weight_tree():
    import jax
    cfg = json.loads((CONFIGS / "granite-3-2b.json").read_text())
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=96,
               vocab_size=50)
    tree = jax.eval_shape(lambda k: dense_decoder.make_params(cfg, k),
                          jax.random.PRNGKey(0))
    mats = [x.size for path, x in
            jax.tree_util.tree_leaves_with_path(tree)
            if "n1" not in str(path) and "n2" not in str(path)
            and "norm" not in str(path)]
    c = dense_decoder.counts(cfg)
    assert sum(mats) == c["blocks"] + c["unembed"]


def test_peaks_of_the_v5e():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2 ** 30


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
