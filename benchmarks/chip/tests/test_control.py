"""The control (the reference in three bfloat16 passes, in the program's
place) fails the comparison at the configurations' limits, and the
program passes it, on the same rows: at a size a test run holds."""
import json

import pytest

import control
import layout


@pytest.mark.parametrize("name", ["synth50k", "spectra52k"])
def test_control_fails_and_program_passes(name):
    cfg = json.loads((layout.HERE / "configs" / f"{name}.json").read_text())
    cfg.update(n_s=3000, n_r=3000)
    traffic = {"rows_per_call": 1024, "check_rows": 256}
    limits = dict(cfg["check"], bad_rows=0)
    ctl = control.readings(cfg, traffic, 11, control=True)
    prog = control.readings(cfg, traffic, 11, control=False)
    assert not control.oracle.verdict(ctl, limits), ctl
    assert control.oracle.verdict(prog, limits), prog


@pytest.mark.parametrize("mix", ["serve", "overload"])
def test_control_fails_on_the_rows_a_served_check_draws(mix):
    """Each row compared over the ranks its request asked for; the
    program's side of a served cell is its runs' own check."""
    cfg = json.loads((layout.HERE / "configs" / "synth50k.json").read_text())
    cfg.update(n_s=3000, n_r=3000)
    traffic = layout.traffic(mix)
    limits = dict(cfg["check"], bad_rows=0)
    ctl = control.readings(cfg, traffic, 11, control=True, seconds=30)
    assert not control.oracle.verdict(ctl, limits), ctl
    with pytest.raises(ValueError):
        control.readings(cfg, traffic, 11, control=False, seconds=30)
