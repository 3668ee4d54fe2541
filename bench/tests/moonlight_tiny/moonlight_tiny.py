"""Plain reference of the test-only `moonlight_tiny`: the plain model of
`moonlight_16b_a3b` (`bench/configs/moonlight_16b_a3b.py`), under the
small widths of the JSON beside this file."""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "bench_plain_moonlight", os.path.join(HERE, "..", "..", "configs",
                                          "moonlight_16b_a3b.py"))
_plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_plain)
with open(os.path.join(HERE, "moonlight_tiny.json")) as _f:
    CFG = json.load(_f)

init, forward_flops, data_loss = _plain.init, _plain.forward_flops, \
    _plain.data_loss


def loss(params, xb, yb):
    del yb
    return data_loss(CFG, params, xb)
