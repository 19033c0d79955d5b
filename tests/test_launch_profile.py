"""The training launcher's profile (``--profile-dir``/``--profile-steps``):
one ``train`` step event per profiled step, holding the data layer's and
the loop's host spans."""
import argparse
import glob

import pytest

from repro.launch import train

SPANS = ("data.batch_at", "train.put_batch", "train.dispatch", "train.wait")


def _host_events(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
    return out


def test_profile_holds_one_step_event_per_step_with_its_spans(
        tmp_path, monkeypatch, capsys):
    # the launcher turns on the persistent compile cache; keep it here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    prof = tmp_path / "prof"
    train.main(["--smoke", "--steps", "3", "--seq-len", "32",
                "--global-batch", "2", "--log-every", "1",
                "--profile-dir", str(prof), "--profile-steps", "1:3"])
    assert "recompiled" not in capsys.readouterr().out
    paths = glob.glob(str(prof / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    events = _host_events(paths[0])
    steps = sorted((s, e) for n, s, e in events if n == "train")
    assert len(steps) == 2                      # steps 1 and 2
    for name in SPANS:
        inside = [(s, e) for n, s, e in events if n == name]
        assert len(inside) == 2, name
        for s, e in steps:
            assert sum(1 for a, b in inside if s <= a and b <= e) == 1, name


def test_profile_steps_parse():
    assert train._step_range("2:5") == range(2, 5)
    for bad in ("3:3", "5:2", "-1:2"):
        with pytest.raises(argparse.ArgumentTypeError):
            train._step_range(bad)
