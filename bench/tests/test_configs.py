"""Configuration files: parameter counts and pool page bytes as reckoned
by hand, the program's ArchConfig built from the file's own numbers, and
the peaks table."""

import os

import pytest

import run


def _cfg(name):
    cfg = run.load_json(os.path.join(run.BENCH, "configs", name + ".json"))
    ref = run.load_module(os.path.join(run.BENCH, "configs",
                                       cfg["reference"] + ".py"))
    return cfg, ref


@pytest.mark.parametrize("name,params,page", [
    ("internlm2-20b", 5_818_177_536, 406_272),
    ("mistral-7b-v0.3", 3_758_231_552, 541_696)])
def test_params_and_page_bytes(name, params, page):
    cfg, ref = _cfg(name)
    assert ref.param_count(cfg) == params
    assert ref.page_bytes(cfg, cfg["serving"]["page_size"]) == page


@pytest.mark.parametrize("name", ["internlm2-20b", "mistral-7b-v0.3"])
def test_program_config_matches_the_file(name):
    from repro.serving.kv_pool import page_bytes

    cfg, ref = _cfg(name)
    acfg = run.program_module(cfg).arch_config(cfg)
    assert acfg.total_params() == ref.param_count(cfg)
    assert page_bytes(acfg, 16) == ref.page_bytes(cfg, 16)
    assert acfg.norm_eps == cfg["rms_norm_eps"]
    assert acfg.rope_theta == cfg["rope_theta"]


def test_peaks_refuse_an_unknown_device_kind():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        run.peaks_for("TPU v9 imaginary")


def test_every_named_file_exists():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(run.ROOT, c["file"]))
    for w in bench["workloads"]:
        run.resolve(w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                           m["name"] + ".py"))


def test_a_run_without_a_tpu_refuses():
    # the tests run on the CPU: the harness must not fall back to it
    with pytest.raises(SystemExit, match="no TPU"):
        run.check_device(1)
