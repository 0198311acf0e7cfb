"""`runs/handover_microbench.py --allow-cpu`: the step-0 microbenchmark of PR 43
runs end to end at tiny shapes on the CPU (a smoke test of its paths, not a
reading: its numbers mean something on the chip only), and every form of the
replay -> model hand-over it times gives conv1 the same bits as the parent's."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _microbench():
    spec = importlib.util.spec_from_file_location(
        "handover_microbench", os.path.join(ROOT, "runs", "handover_microbench.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("cell", ["nature", "lru", "dp4"])
def test_every_form_of_the_hand_over_gives_conv1_the_parents_bits(cell, capsys, tmp_path):
    bench = _microbench()
    B, T, W = bench.TINY[cell][:3]
    # the seam's split where the cell has one, the one call where it has none
    assert (W < T) == (bench.CELLS[cell][2] < bench.CELLS[cell][1])
    rc = bench.main(["--allow-cpu", "--reps", "1", "--cells", cell, "--hlo-dir", str(tmp_path)])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0
    readings = [l for l in lines if "form" in l]
    assert [l["form"] for l in readings] == list(bench.FORMS) == ["bt", "merged", "merged_bf16", "rows", "unshared"]
    for l in readings:
        assert l["bit_equal_at_conv_input"] is True and l["update_ms"] > 0
        assert (l["cell"], l["rows"], l["T"], l["frames_with_gradient"]) == (cell, B, T, B * W)
    assert sorted(lines[-1]["faster_than_bt_ms"]) == ["merged", "merged_bf16", "rows", "unshared"]
    assert sorted(os.listdir(tmp_path)) == sorted(f"{cell}.{form}.txt" for form in bench.FORMS)
    # `unshared` changes the seam's path alone: with no seam it compiles to `merged`'s text
    merged, unshared = ((tmp_path / f"{cell}.{form}.txt").read_text() for form in ("merged", "unshared"))
    assert (merged == unshared) == (W == T)


def test_it_reads_nothing_without_a_chip(capsys):
    assert _microbench().main(["--cells", "dp4"]) == 3
    assert "no TPU" in capsys.readouterr().err
