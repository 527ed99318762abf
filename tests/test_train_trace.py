"""The trainer's profiler trace: ``--trace-dir`` writes the host spans of the
traced steps beside the device operations, and without the flag the
profiler is never started."""
import jax
import pytest

from repro.launch import train

ARGS = ["--arch", "sppo-gpt-7b", "--reduced", "--layers", "2", "--mesh",
        "1x1", "--offload-moments", "--n-chunks", "2", "--steps", "3",
        "--seq", "128", "--batch", "1", "--log-every", "10"]
SPANS = {"sppo.step", "sppo.batch", "sppo.dispatch", "sppo.block",
         "sppo.shard_batch"}


def _host_spans(path):
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(path))
    out = {}
    for plane in prof.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sppo."):
                    out[e.name] = out.get(e.name, 0) + 1
    return out


def test_trace_dir_writes_the_host_spans(tmp_path):
    hist = train.main(ARGS + ["--trace_dir", str(tmp_path / "tr")])
    assert len(hist) == 3
    path, = (tmp_path / "tr").glob("**/*.xplane.pb")
    spans = _host_spans(path)
    assert SPANS <= set(spans)
    # the default two traced steps, from the step after the first
    assert spans["sppo.step"] == 2 and spans["sppo.dispatch"] == 2


def test_no_trace_without_the_flag(tmp_path, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the profiler was started")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.chdir(tmp_path)
    assert len(train.main(ARGS)) == 3
    assert not list(tmp_path.glob("**/*.xplane.pb"))


@pytest.mark.parametrize("steps, traced", [(1, False), (5, True)])
def test_trace_steps_start_after_the_first(tmp_path, monkeypatch, steps,
                                           traced):
    """The traced steps begin at step 1: a one-step run traces nothing."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    args = list(ARGS)
    args[args.index("--steps") + 1] = str(steps)
    train.main(args + ["--trace-dir", str(tmp_path)])
    assert calls == (["start", "stop"] if traced else [])
