import copy
import dataclasses
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
REDUCED_LOGIT_ERR_MAX = 0.09


def reduced(cell):
    """``cell`` at its family's CPU-test widths and a few sessions; its
    architecture flags, dtype, traffic shape and limits stay, but for the
    limit of ``logit_err_max``."""
    from bench.cell import family_module

    cfg = family_module(cell.config["family"], cell.root).reduced(copy.deepcopy(cell.config))
    tr = copy.deepcopy(cell.traffic)
    tr.update(clients=4, sessions=16, context_cap_tokens=64)
    tr["history"] = dict(tr["history"], hi=56)
    tr["user_tokens"] = dict(tr["user_tokens"], mean=6, lo=2, hi=16)
    tr["output_tokens"] = dict(tr["output_tokens"], mean=12, lo=2, hi=24)
    # logit_err_max grows with width; at these widths on the CPU the
    # program read 0.040-0.056 over 8 seeds and the int8 control
    # 0.14-0.19, so the limit lies between them
    wl = copy.deepcopy(cell.workload)
    wl["limits"]["logit_err_max"] = REDUCED_LOGIT_ERR_MAX
    return dataclasses.replace(cell, workload=wl, config=cfg, traffic=tr)


@pytest.fixture
def reduced_cell():
    return reduced
