import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(BENCH, "traffic"), os.path.join(BENCH, "metrics")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest


class FakeChip:
    """Stands in for the TPU the harness looks for, so that a test can
    drive the rest of a run on the CPU (kernels in interpret mode)."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        # 4 MiB beside the 1 GiB reserve: a pool of a few hundred pages
        return {"bytes_limit": 2**30 + 2**22, "bytes_in_use": 0,
                "peak_bytes_in_use": 1}


@pytest.fixture
def tiny_spec():
    """A cell at a size a CPU test run holds: the dense GQA reference at
    d_model 64, two layers, 4 query heads over 2 kv heads, and a short
    open-loop mix that mixes greedy and sampled requests. Its limits are
    set from this size's own sound runs (max_logit_gap 6e-4 and
    max_logprob_err 5e-3 on the CPU)."""
    import run

    cfg = run.load_json(os.path.join(BENCH, "configs", "internlm2-20b.json"))
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=2, vocab_size=256)
    cfg["serving"] = dict(cfg["serving"], max_slots=4, prefill_chunk=16)
    cfg["correct"] = {"max_logit_gap": 0.02, "max_logprob_err": 0.05}
    mix = {"arrival": "poisson", "rate_per_s": 3.0, "warmup_s": 1.0,
           "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 48},
           "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
           "sampling": {"temperature": 0.7, "top_p": 0.9},
           "greedy_share": 0.5}
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    return {"cell": {"name": "tiny.chat", "chips": 1}, "config": cfg,
            "traffic": mix, "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}
