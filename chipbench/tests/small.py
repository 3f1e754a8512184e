"""A cell at a CPU-sized model and load, for the harness's dry runs:
the cell's own traffic shape and service settings, with the model's
widths, the contexts and the lengths cut down, and the Pallas kernels
in interpret mode."""
import shutil
import tempfile
import time
from pathlib import Path

from chipbench import harness
from chipbench.traffic import generate as gen

SMALL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=8192)
# wide enough that an int8 copy of the weights departs from the bf16
# logits by more than the decode cell's limits (at SMALL it does not)
WIDE = dict(SMALL, hidden_size=1024, intermediate_size=2048,
            num_attention_heads=8, num_key_value_heads=2, head_dim=128)


def small(workload: str, size: dict = SMALL):
    bench = harness.load_benchmark()
    spec = harness.cell_spec(bench, workload)
    cfg = harness.load_config(bench, spec["config"])
    cfg.update(size)
    mix = gen.load(spec["traffic"])
    mix["contexts"] = min(mix["contexts"], 6)
    mix["history"] = {"dist": "uniform", "lo": 40, "hi": 200}
    mix["prompt"]["prompt_share"] = 0.15
    mix["output"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                     "lo": 2, "hi": 12}
    mix["service"]["max_ctx"] = 512
    mix["service"].pop("pool_pages_16", None)
    mix["rate_per_s"] = 2.0
    mix["drain_s"] = 30 if mix["drain_s"] else 0
    mix["check"].update(tokens=40, max_calls=6, min_tokens=10)
    return bench, cfg, mix


def end_to_end(workload: str) -> set:
    bench = harness.load_benchmark()
    return {m["name"] for m in harness.metrics_for(bench, workload, False)}


def interpret_kernels(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_mode", lambda force: force or "interpret")


def run(workload, seed=2**31 + 7, seconds=5.0, trace=False, controls=(),
        size=SMALL):
    """One small run, with its swap files and trace in a directory of its
    own (test files run side by side in several processes)."""
    bench, cfg, mix = small(workload, size)
    work, harness.WORK = harness.WORK, Path(tempfile.mkdtemp())
    try:
        return harness.run(bench, workload, seed, seconds, trace,
                           time.perf_counter(), cfg=cfg, mix=mix,
                           controls=controls, log=lambda s: None)
    finally:
        shutil.rmtree(harness.WORK, ignore_errors=True)
        harness.WORK = work
