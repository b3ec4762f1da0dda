"""The exactness probes under ``tools/`` at ``.reduced()`` widths on the
CPU: they run end to end and print what their docstrings promise."""
import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


def test_row_invariance_probe(capsys):
    probe = _load("row_invariance_probe")
    assert probe.main(["--reduced"]) == 0
    rows = _lines(capsys)
    dots = [r for r in rows if r["probe"] == "dot"]
    decode = {r["kv_quant"]: r for r in rows if r["probe"] == "decode"}
    assert len(dots) == 6 and set(decode) == {"none", "int8"}
    n = len(probe.TS)
    for r in dots:
        assert len(r["n_diff"]) == n and r["n_diff"][0] == 0
    for r in decode.values():
        assert r["precision"] == "default" and r["backend"] == "cpu"
        assert len(r["logit_max_diff"]) == n
        # T = 1 against itself, and only ulps of logit beyond
        assert r["logit_max_diff"][0] == 0 and r["kv_n_diff"][0] == 0
        assert max(r["logit_max_diff"]) < 1e-3 * r["logit_std"]


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_exactness_probe(capsys, precision):
    probe = _load("exactness_probe")
    assert probe.main(["--reduced", "--requests", "2", "--max-new", "6",
                       "--seeds", "0", "1", "--precision", precision]) == 0
    rows = [r for r in _lines(capsys) if "divergences" in r]
    assert [(r["precision"], r["seed"]) for r in rows] == [
        (precision, 0), (precision, 1)]
    for r in rows:
        assert r["divergences"] == {"fp/dsde/model": 0, "fp/dsde/ngram": 0,
                                    "int8/dsde/model": 0}
