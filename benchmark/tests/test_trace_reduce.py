"""The trace reduction against hand counts on a small recorded trace.

``data/small_trace.xplane.pb`` is ``data/small_trace.pbtxt`` serialized
(``test_recorded_trace_is_the_text``).  Hand counts, in ns, window
[1000, 11000] (10000 ns):

TPU:0 leaves (``while.1`` holds ops inside it, so it is a container and
dropped): copy.7 [0,1500] -> [1000,1500]; fusion.1 [1000,3000];
chol_base (tpu_custom_call) [3000,4000]; all-reduce.3 [3500,5000];
fusion.4 [6000,7000]; all-gather-start.5 [8000,9500]; fusion.6
[10500,12000] -> [10500,11000].
  busy union [1000,5000] + [6000,7000] + [8000,9500] + [10500,11000]
  = 4000 + 1000 + 1500 + 500 = 7000
  mosaic 1000; collective union [3500,5000] + [8000,9500] = 3000;
  exposed (not under compute [1000,4000], [6000,7000], [10500,11000]):
  [4000,5000] + [8000,9500] = 2500.
TPU:1: fusion.1 [2000,4000]: busy 2000, nothing else.
Averaged over the two: busy 4500, idle share 0.55, mosaic 500,
collective 1500; exposed on the worst device 2500.
"""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
PB = os.path.join(DATA, "small_trace.xplane.pb")
NS = 1e-9


@pytest.fixture(scope="module")
def pdata():
    return tr.load(PB)


def test_recorded_trace_is_the_text():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = ProfileData.from_text_proto(f.read())

    def events(pd):
        return sorted((p.name, ln.name, e.name, e.start_ns, e.end_ns,
                       tuple(e.stats))
                      for p in pd.planes for ln in p.lines
                      for e in ln.events)

    assert events(text) == events(tr.load(PB))


def test_window(pdata):
    assert tr.window_of(pdata) == (1000, 11000)


def test_per_device(pdata):
    red = tr.reduce(pdata)
    d0, d1 = red["per_device"][0], red["per_device"][1]
    assert d0["busy_s"] == pytest.approx(7000 * NS)
    assert d0["mosaic_s"] == pytest.approx(1000 * NS)
    assert d0["collective_s"] == pytest.approx(3000 * NS)
    assert d0["exposed_collective_s"] == pytest.approx(2500 * NS)
    assert d1["busy_s"] == pytest.approx(2000 * NS)
    assert d1["mosaic_s"] == 0 and d1["collective_s"] == 0


def test_averages(pdata):
    red = tr.reduce(pdata)
    assert red["window_s"] == pytest.approx(10000 * NS)
    assert red["busy_s"] == pytest.approx(4500 * NS)
    assert red["idle_share"] == pytest.approx(0.55)
    assert red["mosaic_s"] == pytest.approx(500 * NS)
    assert red["collective_s"] == pytest.approx(1500 * NS)
    assert red["exposed_collective_s_max"] == pytest.approx(2500 * NS)


def test_only_the_cells_devices(pdata):
    red = tr.reduce(pdata, devices=[0])
    assert red["busy_s"] == pytest.approx(7000 * NS)
    assert red["idle_share"] == pytest.approx(0.3)


def test_classification():
    assert tr.is_collective("%all-reduce.3 = f32[8]{0} all-reduce(...)")
    assert tr.is_collective("all-gather-start.5")
    assert tr.is_collective("%collective-permute-done = f32[2] ...")
    assert not tr.is_collective("%fusion.1 = f32[2] fusion(%all-reduce.3)")
    assert tr.is_mosaic('%solve.163 = f32[256,256] custom-call(%c), '
                        'custom_call_target="tpu_custom_call"')
    assert not tr.is_mosaic('%custom-call.1 = f32[8192,8192] custom-call('
                            '%A.1), custom_call_target="X64SplitLow"')
    assert tr.op_name("%solve.163 = f32[2] custom-call()") == "solve.163"


def test_breakdown(pdata):
    red = tr.reduce(pdata)
    b = tr.breakdown(pdata, red)
    ops = dict((n, t) for n, t in b["device_ops"])
    # fusion: 2000 + 1000 + 500 on TPU:0, 2000 on TPU:1, averaged
    assert ops["fusion"] == pytest.approx(2750 * NS)
    assert ops["chol_base"] == pytest.approx(500 * NS)
    gaps = b["idle_gaps"]
    # TPU:0 idle: [5000,6000], [7000,8000], [9500,10500]
    assert [g[1] for g in gaps] == pytest.approx([1000 * NS] * 3)
    names = sorted(g[0] for g in gaps)
    assert names == ["PjitFunction(solve)", "host idle", "np.asarray"]


def test_union_and_subtract():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                          (6, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
