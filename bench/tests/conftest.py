"""Shared helpers of the benchmark's CPU tests: cells cut to smoke size.

The smoke cells keep each cell's structure (layers, groups, engine,
traffic) at sizes a CPU runs in seconds, with float32 activations so
that a sound run reads far below every limit and a fault reads above it.
"""

import copy
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
             vocab_size=256, dtype="float32")


def smoke_cell(name: str):
    from bench import harness
    cell = harness.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["model"].update(SMOKE)
    if cell.traffic["kind"] == "compress":
        cell.traffic.update(calib_sequences=4, calib_tokens=32)
        cell.traffic["compress"]["microbatch"] = 2
        if cell.traffic["compress"]["refine"]:
            cell.traffic["compress"]["refine_epochs"] = 10
    else:
        # the logits' spread, which the gap is read against, grows with
        # the width through the tied embedding: serve at d_model 1024
        cell.config["model"].update(d_model=1024, num_heads=8,
                                    num_kv_heads=4, head_dim=128,
                                    d_ff=256, num_layers=2)
        cell.config["serve"].update(slots=4, max_len=96, requests_per_s=1.0)
        cell.traffic.update(prompt_tokens=[16, 40], output_tokens=[8, 24],
                            check_requests=4)
    return cell


@pytest.fixture
def smoke():
    return smoke_cell
