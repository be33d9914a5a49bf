"""The slab budgeted by bytes: a replica's rows are ``session_capacity + 1``
where its share of the device's free memory holds them, fewer where it
does not (larger rows, less memory), never fewer than a full wave and the
scratch row, and each replica on a device sizes its rows from what the
others left."""
import jax
import pytest

from repro.runtime import InferenceEngine, TopologySpec
from repro.runtime import node as node_mod
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.node import SLAB_MEMORY_SHARE, SlabBudgetError, slab_rows
from repro.runtime.wire import WireCodec
from tests._worker_graphs import lm_graph
from tests.test_hybrid_decode import graph as hybrid_graph

CODECS = DispatcherCodecs(data=WireCodec("raw", "none"),
                          weights=WireCodec("raw", "none"))


def test_rows_fall_as_row_bytes_rise_and_stop_at_the_floor():
    free = 1 << 30
    share = int(SLAB_MEMORY_SHARE * free)
    assert slab_rows(64, 17, 1 << 20, free) == 65        # 65 MiB fits
    rows = [slab_rows(64, 17, row, free)
            for row in (4 << 20, 8 << 20, 12 << 20)]
    assert rows == [share // (4 << 20), share // (8 << 20),
                    share // (12 << 20)]
    assert rows[0] > rows[1] > rows[2] >= 17
    with pytest.raises(SlabBudgetError, match="17 rows"):
        slab_rows(64, 17, 16 << 20, free)                # 16 rows fit
    assert slab_rows(64, 17, 1 << 40, None) == 65        # no figures


def _engine(g, free, capacity=64, max_batch=4):
    eng = InferenceEngine(g, TopologySpec.chain(g, 2,
                                                session_capacity=capacity),
                          codecs=CODECS, max_batch=max_batch)
    eng.configure(g.init(jax.random.PRNGKey(0)))
    return eng


def _rows(eng):
    return [n._slab_rows for n in eng.dispatcher.nodes]


@pytest.mark.parametrize("make,full", [
    (lm_graph, True),              # small rows: session_capacity + 1
    (hybrid_graph, False),         # stage 1's rows hold a 160-slot cache
])
def test_each_replica_sizes_its_slab_from_what_is_left(monkeypatch, make,
                                                       full):
    """One stubbed figure of free memory for the device: stage 0 sizes its
    slab first, stage 1 from what stage 0 left; a graph of small rows
    keeps ``session_capacity + 1`` rows, the hybrid's stage 1 fewer."""
    free = 8 << 20
    monkeypatch.setattr(node_mod, "free_memory", lambda device: free)
    g = make()
    eng = _engine(g, free)
    try:
        eng.precompile()
        got = _rows(eng)
        n0, n1 = eng.dispatcher.nodes
        left = free - got[0] * sum(n0._row_bytes)
        assert got[1] == min(65, int(SLAB_MEMORY_SHARE * left)
                             // sum(n1._row_bytes))
        assert got[0] == 65 and (got[1] == 65) == full
        assert got[1] >= 5                        # max_batch + 1
        assert [n.sessions.capacity for n in (n0, n1)] == [r - 1 for r in got]
        eng.start()
        toks = list(eng.generate([3, 1, 4, 1, 5], 3, session_id="b"))
        assert len(toks) == 3
        for n, r in zip((n0, n1), got):
            leaves = jax.tree_util.tree_leaves(n._slab)
            assert leaves and all(a.shape[0] == r for a in leaves)
            assert n.stats.snapshot()["slab_rows"] == r
    finally:
        eng.shutdown()


def test_a_slab_below_a_full_wave_fails_the_open(monkeypatch):
    """Memory for fewer than ``max_batch + 1`` rows: the open fails loudly
    and names the budget."""
    monkeypatch.setattr(node_mod, "free_memory", lambda device: 1 << 10)
    g = lm_graph()
    eng = _engine(g, 1 << 10)
    try:
        eng.start()
        with pytest.raises(Exception) as failed:
            list(eng.generate([3, 1, 4], 2, session_id="f", restart="never"))
        assert "SlabBudgetError: a slab of 5 rows" in str(
            failed.value.__cause__)
    finally:
        eng.shutdown()
