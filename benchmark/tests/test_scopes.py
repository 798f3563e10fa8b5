"""Device time per program phase against hand counts on a small trace.

``data/scoped_trace.xplane.pb`` is ``data/scoped_trace.pbtxt`` serialized
(``test_recorded_trace_is_the_text``).  Window [1000, 21000] ns, two
traced solves.  TPU:0 leaves (``while.1`` is a container, dropped), in
ns, by the phase on the scope path of their event metadata's ``tf_op``:

  getrf.panel   fusion.2        3000 + 3000
  getrf.swap    gather.3        1000 + 1000
  getrf.trsm    custom-call.4    500 +  500
  getrf.update  fusion.5        2500 + 2500
  getrs.*       fusion.6, fusion.7, gather.8   1000 + 1000 + 400
  none          copy.1 (a path with no phase) 1000 + 1000;
                broadcast.9 (no path) [20500, 21500] -> 500
  busy 18900, so 9450 per solve: panel 3000, swap 1000, trsm 500,
  update 2500, getrs 1200, unscoped 1250 (13.2275...% of busy), and
  the phases plus the unscoped part are busy exactly.
"""

import os
import shutil

import pytest

import harness
import scopes
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
PB = os.path.join(DATA, "scoped_trace.xplane.pb")
UNSCOPED_PB = os.path.join(DATA, "small_trace.xplane.pb")
NS = 1e-9
PER_SOLVE_MS = {"getrf.panel_ms": 3000e-6, "getrf.swap_ms": 1000e-6,
                "getrf.trsm_ms": 500e-6, "getrf.update_ms": 2500e-6,
                "getrs_ms": 1200e-6}
READERS = tuple(PER_SOLVE_MS) + ("unscoped_share",)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A run's inputs for the readers, with ``pb`` laid out as the
    harness's tracer leaves its profile in the temporary directory."""
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))

    def make(pb, solves=2):
        d = tmp_path / "bench_trace_x" / "plugins" / "profile" / "1"
        d.mkdir(parents=True, exist_ok=True)
        shutil.copy(pb, d / "host.xplane.pb")
        return {"trace": tr.reduce(tr.load(pb)), "solves": solves}

    return make


def test_recorded_trace_is_the_text(tmp_path):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "scoped_trace.pbtxt")) as f:
        text = ProfileData.text_proto_to_serialized_xspace(f.read())
    (tmp_path / "t.xplane.pb").write_bytes(text)

    def events(pd):
        return sorted((p.name, ln.name, e.name, e.start_ns, e.end_ns)
                      for p in pd.planes for ln in p.lines
                      for e in ln.events)

    assert events(tr.load(str(tmp_path / "t.xplane.pb"))) == \
        events(tr.load(PB))
    assert scopes.metadata_paths(str(tmp_path / "t.xplane.pb")) == \
        scopes.metadata_paths(PB)


def test_scope_of_by_hand():
    names = {"copy.1", "fusion.2", "gather.3", "custom-call.4", "fusion.5",
             "fusion.6", "fusion.7", "gather.8", "broadcast.9"}
    assert scopes.scope_of(PB, names) == {
        "copy.1": None, "fusion.2": "getrf.panel", "gather.3": "getrf.swap",
        "custom-call.4": "getrf.trsm", "fusion.5": "getrf.update",
        "fusion.6": "getrs.trsm_lower", "fusion.7": "getrs.trsm_upper",
        "gather.8": "getrs.permute", "broadcast.9": None}


def test_metadata_paths_by_hand():
    """One path per distinct op, read from the event metadata (a
    referenced stat value too), with the ``:<op_type>`` cut off."""
    paths = scopes.metadata_paths(PB)
    assert len(paths) == 9 and "broadcast.9" not in paths
    assert paths["gather.8"] == "jit(solve)/getrs.permute/gather"
    assert paths["copy.1"] == "jit(solve)/transpose"


def test_phase_of_takes_the_innermost_phase():
    assert scopes.phase_of("jit(solve)/while/body/closed_call/"
                           "getrf.panel/while/body/dynamic_slice") == \
        "getrf.panel"
    assert scopes.phase_of("jit(f)/getrs.trsm_lower/jit(g)/getrf.trsm/x") \
        == "getrf.trsm"
    assert scopes.phase_of("jit(solve)/while/body/closed_call/dot_general") \
        is None


@pytest.mark.parametrize("name", sorted(PER_SOLVE_MS))
def test_phase_reader_by_hand(traced, name):
    run = traced(PB)
    value = harness.reader(name).read(run)
    assert value == pytest.approx(PER_SOLVE_MS[name])


def test_unscoped_share_by_hand(traced):
    run = traced(PB)
    assert harness.reader("unscoped_share").read(run) == \
        pytest.approx(100 * 1250 / 9450)


def test_phases_and_unscoped_add_up_to_busy(traced):
    run = traced(PB)
    per = scopes.phases(run)
    assert run["trace"]["busy_s"] == pytest.approx(18900 * NS)
    assert sum(per.values()) == pytest.approx(run["trace"]["busy_s"] / 2)
    phased = sum(harness.reader(n).read(run) for n in PER_SOLVE_MS)
    unscoped = harness.reader("unscoped_share").read(run) / 100 * 9450e-6
    assert phased + unscoped == pytest.approx(9450e-6)


def test_phase_busy_averages_over_devices():
    red = {"ops": {0: [(0, 10, "a", "op"), (5, 20, "b", "op")],
                   1: [(0, 30, "a", "op")]}}
    assert scopes.phase_busy(red, {"a": "x.y"}) == pytest.approx(
        {"x.y": 20e-9, None: 7.5e-9})


def test_the_profile_of_this_window_is_read(traced, tmp_path):
    """Another run's profile, newer, is passed over by its window."""
    run = traced(PB)
    other = tmp_path / "bench_trace_y" / "plugins" / "profile" / "2"
    other.mkdir(parents=True)
    shutil.copy(UNSCOPED_PB, other / "host.xplane.pb")
    os.utime(other / "host.xplane.pb", (2e9, 2e9))
    assert harness.reader("getrf.panel_ms").read(run) == \
        pytest.approx(PER_SOLVE_MS["getrf.panel_ms"])


@pytest.mark.parametrize("name", READERS)
def test_program_without_phases_reads_nothing(traced, name):
    """The parent program has no phase scopes: no reading, no error."""
    assert harness.reader(name).read(traced(UNSCOPED_PB)) is None


@pytest.mark.parametrize("name", READERS)
def test_no_profile_reads_nothing(tmp_path, monkeypatch, name):
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))
    run = {"trace": tr.reduce(tr.load(PB)), "solves": 2}
    assert harness.reader(name).read(run) is None
    assert harness.reader(name).read({"trace": None, "solves": 0}) is None
