"""Helpers of the benchmark's CPU tests."""
import copy
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {"num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 64, "d_ff": 512, "vocab_size": 1024, "microbatches": 2}


def tiny_cell(workload: str, **traffic):
    """The cell as BENCHMARK.json has it, at a size a CPU test can hold:
    the configuration's widths shrunk, batch 4 x 64."""
    from bench.run import load_cell
    cell = copy.deepcopy(load_cell(workload))
    cell.model.update(TINY)
    cell.traffic.update({"batch": 4, "seq": 64, **traffic})
    return cell

