"""The benchmark's own tests (CPU): `python -m pytest bench/tests`."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import copy  # noqa: E402

# the paper fleet at a size a CPU test run holds, through the same
# registry scenario and engine
TINY_OVERRIDES = ("data.num_workers=4", "data.n_local=64",
                  "model.width_mult=2", "algo.batch_size=16")


def tiny(cell: str):
    """(ctx, cfg, overrides) of a paper cell shrunk for the CPU."""
    import run
    ctx = run.load_cell(cell)
    cfg = copy.deepcopy(ctx["cfg"])
    cfg["model"]["width_mult"] = 2
    return ctx, cfg, TINY_OVERRIDES
