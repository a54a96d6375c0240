"""``chip_smoke.py``'s phases at a tiny size on the CPU (Pallas in
interpret mode), and its refusal to report anything without a TPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


def test_phase_tick_matches_numpy(configdict):
    log = chip_smoke.phase_tick(configdict, J=256, pools=(2, 3, 3),
                                iters=4, churn=16, on_chip=False,
                                emit=lambda *_: None)
    assert len(log) == 4 and any(log)


def test_phase_sim_matches_numpy(configdict):
    got = chip_smoke.phase_sim(configdict, n_jobs=60, pools=(1, 2, 2),
                               on_chip=False, emit=lambda *_: None)
    assert len(got) == 60


def test_phase_engine_reduced(configdict):
    lines = []
    rel = chip_smoke.phase_engine(configdict, reduced=True, requests=2,
                                  gen=4, batch=2, prompt_len=16,
                                  on_chip=False, emit=lines.append)
    assert 0 <= rel < chip_smoke.LOGIT_TOL["float32"]
    assert sum(line.startswith("req ") for line in lines) == 2


def test_on_chip_refuses_interpreted_tick(configdict):
    """The device assertion is live unless the caller opts out."""
    with pytest.raises(RuntimeError, match="interpreter"):
        chip_smoke.phase_tick(configdict, J=64, pools=(1, 1, 1), iters=1,
                              churn=4, emit=lambda *_: None)


def test_main_without_tpu_reports_nothing(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert out == ""
