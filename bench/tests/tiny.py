"""A benchmark cell cut to a size a CPU test run can hold: the cell's own
traffic shape and limits, at small widths, short windows and
small pools."""
import copy
import time

from bench import harness, spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def cell_files(name: str) -> spec.Cell:
    """The cell `name` (<config>.<traffic>) from its configuration, traffic
    and limits files, whether or not BENCHMARK.json lists it."""
    config, traffic = name.rsplit(".", 1)
    return spec.Cell(
        name, 1, spec.load_json(spec.BENCH / "configs" / f"{config}.json"),
        spec.load_json(spec.BENCH / "traffic" / f"{traffic}.json"),
        spec.load_json(spec.BENCH / "limits" / f"{name}.json"), [], [])


def tiny_cell(name: str, *, d: int = 256, layers: int = 2, vocab: int = 512,
              context: int = 1024, pages: int = 120, rate: float = 1.0,
              warm_s: float = 1.0, page_size: int = 128, chunk: int = 512):
    cell = cell_files(name)
    cfg = copy.deepcopy(cell.cfg)
    hd = 64
    hq = d // hd
    hkv = max(1, hq * cfg["num_key_value_heads"]
              // cfg["num_attention_heads"])
    ff = "intermediate_size" if "intermediate_size" in cfg else "ffn_dim"
    cfg.update({"num_hidden_layers": layers, "hidden_size": d,
                "num_attention_heads": hq, "num_key_value_heads": hkv,
                "head_dim": hd, "vocab_size": vocab, ff: 2 * d,
                "max_position_embeddings": context})
    cfg["program"].update(n_layers=layers, d_model=d, n_heads=hq,
                          n_kv_heads=hkv, head_dim=hd, vocab_size=vocab,
                          d_ff=2 * d, max_seq=context)
    cfg["serve"].update(num_pages=pages, page_size=page_size,
                        prefill_chunk=chunk)
    mix = copy.deepcopy(cell.mix)
    mix["warm_s"] = warm_s
    if mix["loop"] == "open":
        mix["prompt"]["max"] = min(mix["prompt"]["max"], context // 2)
        mix["max_batch"] = 8
        mix["arrivals"]["rate_per_s"] = rate
    else:
        # lengths are shares of the context: sample more requests, so the
        # comparison covers as many served positions as the full cell's
        full = cell.cfg["max_position_embeddings"]
        mix["sample"]["requests"] *= max(1, full // context)
    cell.cfg, cell.mix = cfg, mix
    return cell


def run(cell, seed: int, seconds: float = 2.0, **kw) -> dict:
    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       device=CPU, peak=PEAK, log=lambda s: None, **kw)
