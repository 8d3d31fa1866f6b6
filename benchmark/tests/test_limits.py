"""The limits of ``correct`` are data (``limits/<cell>.json``), and the rule
that places them can be checked from the readings recorded beside them:

* the upper reading is the smallest of - the control's smallest reading,
  where that is three times the lower reading or more; each fault's
  smallest, where that is ten times the lower or more (a state left
  unchanged: three times);
* the limit lies above the lower reading and below the upper one;
* a number listed as not compared has no upper reading by that rule;
* the control's own smallest readings fail at least one limit of the cell.
"""

import glob
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(glob.glob(os.path.join(HERE, "limits", "*.json")))


def _upper(e):
    """(reading, who) by the rule, or (None, None)."""
    lower, cands = e["lower"], {}
    if e.get("control") is not None and e["control"] >= 3 * lower:
        cands["control"] = e["control"]
    for name, v in e.get("faults", {}).items():
        if v >= (3 if name == "state_unchanged" else 10) * lower:
            cands["fault." + name] = v
    if not cands:
        return None, None
    who = min(cands, key=cands.get)
    return cands[who], who


def _cases(key):
    out = []
    for path in FILES:
        with open(path) as f:
            for name, e in json.load(f).get(key, {}).items():
                out.append(pytest.param(
                    e, id=f"{os.path.basename(path)[:-5]}.{name}"))
    return out


def test_every_cell_has_its_limits():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        cells = {w["name"] for w in json.load(f)["workloads"]}
    assert cells <= {os.path.basename(p)[:-5] for p in FILES}


@pytest.mark.parametrize("e", _cases("limits"))
def test_limit_lies_between_its_two_readings(e):
    upper, who = _upper(e)
    assert upper is not None, "no upper reading: the number cannot be held"
    assert e["upper"] == upper and e["upper_from"] == who
    assert e["lower"] < e["limit"] < upper
    # room on both sides: a tenth at the least
    assert e["limit"] >= 1.1 * e["lower"] and upper >= 1.1 * e["limit"]


@pytest.mark.parametrize("e", _cases("not_compared"))
def test_not_compared_has_no_upper_reading(e):
    assert _upper(e) == (None, None)


@pytest.mark.parametrize("path", FILES, ids=[os.path.basename(p)[:-5]
                                             for p in FILES])
def test_control_fails_a_limit_by_its_recorded_readings(path):
    with open(path) as f:
        limits = json.load(f)["limits"]
    over = [k for k, e in limits.items()
            if e.get("control") is not None and e["control"] > e["limit"]]
    assert over
