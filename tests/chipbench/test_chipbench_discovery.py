"""The harness finds every configuration, traffic mix, limit file and
metric reader by its name, so that a new one is added with files alone."""

import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import spec  # noqa: E402


def test_every_named_part_has_its_file():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        assert spec.config(w["config"])["name"] == w["config"]
        assert spec.traffic(w["traffic"])["kind"]
        assert spec.limits(w["name"])["limits"]
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_a_new_part_is_found_by_adding_files(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, base, ignore=shutil.ignore_patterns("__pycache__"))
    (base / "metrics" / "answer.new.py").write_text("def read(run):\n    return 42.0\n")
    (base / "traffic" / "new-mix.json").write_text('{"kind": "open_loop", "rate_per_s": 1}')
    (base / "configs" / "new-model.json").write_text('{"name": "new-model"}')
    (base / "limits" / "new-cell.json").write_text('{"limits": {"logit_gap": 1.0}}')
    assert spec.reader("answer.new", base)(None) == 42.0
    assert spec.traffic("new-mix", base)["rate_per_s"] == 1
    assert spec.config("new-model", base)["name"] == "new-model"
    assert spec.limits("new-cell", base)["limits"]["logit_gap"] == 1.0


def test_metrics_for_follows_each_workloads_list():
    bench = {"end_to_end": [{"name": "a", "workloads": ["x"]}, {"name": "setup_s"}],
             "per_layer": [{"name": "p", "workloads": ["y"]}]}
    assert [m["name"] for m in spec.metrics_for("x", bench, "end_to_end")] == ["a", "setup_s"]
    assert [m["name"] for m in spec.metrics_for("y", bench, "end_to_end")] == ["setup_s"]
    assert spec.metrics_for("x", bench, "per_layer") == []


def test_a_cell_is_found_by_its_mix_kind():
    from chipbench import run, train

    conf, mix = spec.config("olmo-1b"), spec.traffic("train-2k")
    cell, arch = run.make_cell(conf, mix)
    try:
        assert isinstance(cell, train.Cell) and arch["mlp"] == "swiglu"
    finally:
        cell.close()
    import pytest

    with pytest.raises(ImportError):
        run.make_cell(conf, dict(mix, kind="no_such_kind"))


def test_a_form_that_the_reference_does_not_compute_is_refused():
    import pytest

    from chipbench import reference

    conf = spec.config("olmo-1b")
    for key, value in [("hidden_act", "gelu"), ("mlp", "plain"), ("norm", "layernorm")]:
        with pytest.raises(ValueError):
            reference.arch_of(dict(conf, **{key: value}))
