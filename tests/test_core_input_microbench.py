"""`runs/core_input_microbench.py --allow-cpu`: the step-0 microbenchmark of PR
49 runs end to end at tiny shapes on the CPU (a smoke test of its paths, not a
reading: its numbers mean something on the chip only), and every form of
`_core_input`'s way back to time order that it times gives the entries and
the gradient of the indexed formula the program had."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _microbench():
    spec = importlib.util.spec_from_file_location(
        "core_input_microbench", os.path.join(ROOT, "runs", "core_input_microbench.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("cell", ["nature", "dp4"])
def test_every_form_gives_the_indexed_formulas_core_input_and_gradient(cell, capsys, tmp_path):
    bench = _microbench()
    B, T, W, K = bench.TINY[cell]
    # a window shorter than the sequence, as in the cells: the split engages
    assert T > W and bench.CELLS[cell][1:3] == (85, 45)
    rc = bench.main(["--allow-cpu", "--reps", "1", "--cells", cell, "--hlo-dir", str(tmp_path)])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0
    readings = [l for l in lines if "form" in l]
    assert [l["form"] for l in readings] == list(bench.FORMS)
    assert bench.FORMS[0] == "indexed" and "select_band_latent" in bench.FORMS
    for l in readings:
        assert "refused" not in l and l["update_ms"] > 0
        assert (l["cell"], l["rows"], l["T"], l["window"], l["K"]) == (cell, B, T, W, K)
        # the same entries moved, each once: nothing to round
        assert l["x_max_abs_diff_from_first_form"] == 0.0
        assert l["grad_max_abs_diff_from_first_form"] == 0.0
    assert sorted(lines[-1]["faster_than_indexed_ms"]) == sorted(bench.FORMS[1:])
    assert sorted(os.listdir(tmp_path)) == sorted(f"{cell}.{form}.txt" for form in bench.FORMS)


def test_it_reads_nothing_without_a_chip(capsys):
    assert _microbench().main(["--cells", "dp4"]) == 3
    assert "no TPU" in capsys.readouterr().err
