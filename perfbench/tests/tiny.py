"""Cells of the benchmark cut to a size the CPU runs in seconds, for the
tests in this directory. Widths and depth are small; the code paths, the
traffic's kind and the comparison are those of the real cells."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def tiny_config(config: str | dict) -> dict:
    """A configuration file (by name, or as a dict) cut to a tiny size."""
    c = copy.deepcopy(_load("configs", config) if isinstance(config, str)
                      else config)
    c.update(d_model=64, vocab_size=256, n_heads=4, n_kv_heads=4,
             head_dim=16, d_ff=128, attn_chunk_q=8, attn_chunk_kv=8)
    if "sliding_window" in c:
        # a window shorter than the tiny rows, so that it masks keys
        c["sliding_window"] = 11
        c["pattern"] = [[m, f, 12 if w else 0] for m, f, w in c["pattern"]]
    # nested blocks as repro.configs.reduced_config shrinks them
    if c.get("moe"):
        m = c["moe"]
        m.update(n_experts=4, top_k=min(m["top_k"], 2), d_expert=32,
                 n_shared=min(m.get("n_shared", 0), 1))
    if c.get("mla"):
        m = c["mla"]
        m.update(kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
        if m.get("q_lora_rank", 0) is not None:
            m["q_lora_rank"] = 32
    if c.get("mamba"):
        c["mamba"].update(d_state=4, d_conv=4, expand=2, dt_rank=8)
    if c.get("xlstm"):
        c["xlstm"]["chunk"] = 8
    return c


def tiny_cell(workload: str, config: str, traffic: str, limits: dict,
              **traffic_kw) -> dict:
    tr = copy.deepcopy(_load("traffic", traffic))
    if tr["kind"] == "lc":
        tr.update(batch=2, seq_len=32, steps_per_l=3, iteration_s=1.0,
                  ref_steps=3)
    tr.update(traffic_kw)
    return {"name": workload, "chips": 1, "config": config,
            "traffic": traffic, "config_file": tiny_config(config),
            "traffic_file": tr, "limits": limits,
            "end_to_end": [{"name": "lc_tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}
