"""Operation and byte counts (``bench/work.py``) and the work each driver
reports, on known shapes."""
import json
import pathlib

import pytest

from bench import harness, work

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_counts_add_up():
    w = dict(decisions=10, lane_epochs=4, lane_runs=2,
             node_epochs={"exponential": 3, "weibull": 1})
    flops, nbytes = work.counts(w)
    assert flops == 237 * 10 + 53 * 4 + 5 * 3 + 14 * 1
    assert nbytes == 68 * 2


def test_roofline_names_its_bound():
    peaks = dict(flops_per_s=1e12, hbm_bytes_per_s=1e9)
    w = dict(decisions=1000, lane_epochs=0, lane_runs=1, node_epochs={})
    got = work.roofline(w, peaks)
    assert got["bound"] == "compute"
    assert got["seconds"] == pytest.approx(237e3 / 1e12)
    w["lane_runs"] = 10 ** 6
    assert work.roofline(w, peaks)["bound"] == "memory"


def test_peaks_cover_the_v5e():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert peaks["devices"]["TPU v5 lite"]["flops_per_s"] == 1.97e14
    with pytest.raises(KeyError):
        harness.device_peaks("no such chip")


def test_study_work_at_the_configured_shape():
    spec = harness.load_cell("mc.table4.exp")
    d = harness.make_driver(spec, 5)
    w = d.work(3)
    assert w["decisions"] == 3 * 6 * 4096 * 64 * 3
    assert w["lane_epochs"] == 3 * 6 * 4096 * 64
    assert w["lane_runs"] == 3 * 6 * 4096
    assert w["node_epochs"] == {"exponential": 3 * 4096 * 64 * 4}


def test_rack_study_counts_the_shock_sampler():
    d = harness.make_driver(harness.load_cell("mc.table4.rack"), 5)
    assert d.work(1)["node_epochs"] == {"rack": 4096 * 64 * 4}
