"""`runs/unroll_tail_microbench.py --allow-cpu`: the step-0 microbenchmark of PR
46 runs end to end at tiny shapes on the CPU (a smoke test of its paths, not a
reading: its numbers mean something on the chip only), and every form of the
tail of `unroll` it times reads the Q of the indexed formula the program had."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _microbench():
    spec = importlib.util.spec_from_file_location(
        "unroll_tail_microbench", os.path.join(ROOT, "runs", "unroll_tail_microbench.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("cell", ["nature", "lru", "dp4"])
def test_every_form_of_the_tail_reads_the_indexed_formulas_q(cell, capsys, tmp_path):
    bench = _microbench()
    B, T, L, F, K = bench.TINY[cell]
    # every seam from 0 to the preset's burn-in fits: T >= L + F, as in the cell
    assert T >= L + F and bench.CELLS[cell][1] >= bench.CELLS[cell][2] + bench.CELLS[cell][3]
    rc = bench.main(["--allow-cpu", "--reps", "1", "--cells", cell, "--hlo-dir", str(tmp_path)])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0
    readings = [l for l in lines if "form" in l]
    assert [l["form"] for l in readings] == list(bench.FORMS)
    assert bench.FORMS[0] == "indexed" and "band" in bench.FORMS
    for l in readings:
        assert "refused" not in l and l["update_ms"] > 0
        assert (l["cell"], l["rows"], l["T"], l["window"], l["K"]) == (cell, B, T, L + F, K)
        # the same rows through the same heads; a CPU matmul's last bit moves
        # with the number of rows it is given, and no further
        assert l["q_max_abs_diff_from_first_form"] <= 1e-6
    assert readings[1]["q_max_abs_diff_from_first_form"] == 0.0  # the select alone changes no Q
    assert sorted(lines[-1]["faster_than_indexed_ms"]) == sorted(bench.FORMS[1:])
    assert sorted(os.listdir(tmp_path)) == sorted(f"{cell}.{form}.txt" for form in bench.FORMS)


def test_it_reads_nothing_without_a_chip(capsys):
    assert _microbench().main(["--cells", "dp4"]) == 3
    assert "no TPU" in capsys.readouterr().err
