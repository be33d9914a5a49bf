"""Tiny configurations of the benchmark's cells, for runs on the CPU.

Each keeps the real configuration's and traffic file's keys and cuts only
sizes: ResNet50 at 32x32 images and 10 classes, the decoder at width 64
(four layers, a 1024-token vocabulary) with a 64-slot cache, fewer
clients or sessions, shorter prompts.
"""
import json
import time

from bench import harness, spec

BENCH = spec.load_benchmark()
SEED = 2**33 + 12345          # a seed beyond 32 bits, as the driver's are


def tiny(cell_name: str) -> tuple[dict, dict, dict]:
    cell = spec.find(BENCH["workloads"], cell_name, "workload")
    cfg = spec.load_config(BENCH, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    if cfg["model"] == "resnet":
        cfg.update(image_size=32, num_classes=10)
        cfg["serving"]["max_batch"] = 2
        cfg["correct"]["sample"] = 4
        traffic.update(clients=min(traffic["clients"], 3), inputs=4,
                       warmup_passes=1)
    else:
        cfg.update(vocab_size=1024, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, intermediate_size=128,
                   num_hidden_layers=4, max_position_embeddings=64)
        cfg["serving"]["max_batch"] = 4
        traffic.update(sessions=min(traffic["sessions"], 3),
                       pool=min(traffic["pool"], 5), prompt_median=8,
                       prompt_min=4, prompt_max=16, warmup_tokens=3)
    return cell, cfg, traffic


def window(cell_name: str, seconds: float = 1.5, trace: bool = False,
           seed: int = SEED) -> harness.Window:
    """Set-up and window of a run through the harness, as ``bench/run.py``
    makes them after its look for a chip."""
    cell, cfg, traffic = tiny(cell_name)
    return harness.serve_window(cell, cfg, traffic, seed, seconds, trace,
                                time.perf_counter())


def result(win: harness.Window, trace: bool = False) -> dict:
    """The rest of the run: its result line, printed and parsed back."""
    res = harness.finish(win, harness.cell_metrics(BENCH, win.cell["name"]),
                         trace)
    return json.loads(json.dumps(res))


def run(cell_name: str, seconds: float = 1.5, trace: bool = False) -> dict:
    return result(window(cell_name, seconds, trace), trace)
