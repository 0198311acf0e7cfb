"""`runs/ssd_layout_microbench.py --allow-cpu`: the step-0 microbenchmark of PR
55 runs end to end at tiny widths on the CPU (a smoke test of its paths, not a
reading: its numbers mean something on the chip only), every form of the
Mamba-2 mixer's sequence layouts that it times gives the parent form's three
outputs and gradient to float32 rounding, and `committed` IS the module the
cells run."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _microbench():
    spec = importlib.util.spec_from_file_location(
        "ssd_layout_microbench", os.path.join(ROOT, "runs", "ssd_layout_microbench.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("part", ["norm", "ssd", "all"])
def test_every_form_gives_the_parent_forms_outputs_and_gradient(part, capsys, tmp_path):
    bench = _microbench()
    forms = ["parent"] + [f for f in bench.FORMS if f.startswith(part)] + ["committed"]
    assert len(forms) > 3 and bench.FORMS["parent"] == bench.PARENT and bench.FORMS["committed"] is None
    # 21 steps in chunks of 8: the padding to whole chunks engages in every form
    assert bench.TINY_SHAPE["T"] % bench.TINY_WIDTHS["chunk_size"] and bench.SHAPE["T"] % bench.WIDTHS["chunk_size"]
    rc = bench.main(["--allow-cpu", "--reps", "1", "--forms", *forms, "--hlo-dir", str(tmp_path)])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0
    readings = [l for l in lines if "form" in l]
    assert [l["form"] for l in readings] == forms
    for l in readings:
        assert "refused" not in l and l["update_ms"] > 0
        assert (l["rows"], l["T"], l["K"]) == tuple(bench.TINY_SHAPE.values())
        assert l["outputs_diff_over_scale_from_first_form"] < 2e-6
        assert l["grads_diff_over_scale_from_first_form"] < 1e-5
    assert sorted(lines[-1]["faster_than_parent_ms"]) == sorted(forms[1:])
    assert sorted(os.listdir(tmp_path)) == sorted(f"{form}.txt" for form in forms)


def test_the_cells_widths_are_the_configurations():
    bench = _microbench()
    with open(os.path.join(ROOT, "benchmark", "configs", "nemotron-twotower-30b-a3b-ep16.json")) as fh:
        conf = json.load(fh)
    flat = json.dumps(conf)
    for key, value in bench.WIDTHS.items():
        assert f'"{key}": {value}' in flat, key


def test_it_reads_nothing_without_a_chip(capsys):
    assert _microbench().main(["--forms", "parent"]) == 3
    assert "no TPU" in capsys.readouterr().err
