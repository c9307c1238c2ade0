"""ISSUE 24: the spans that name the host's side of an idle gap.

Tracer: a thread's outermost span may name its cause (`parent=`), the
calling thread's innermost open span is readable, and records and the
Chrome export carry the thread's name. Input pipeline: `data.wait` on
the consumer, `data.load` / `data.put` / `data.full` on the producer
thread, `data.transfer` closed by the watcher once the batch is on the
device — and none of it, not even the watcher thread, without a tracer.
ISSUE 25 adds `data.recycle`, the producer's wait for a staging slot.
Serve: `serve.refill`, `serve.start_prefill`, `serve.insert` and the
detached `serve.turnaround` from collect's return to the next dispatch.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu import mesh as meshlib
from idc_models_tpu.data import pipeline, synthetic
from idc_models_tpu.data.idc import ArrayDataset
from idc_models_tpu.data.pipeline import Loader
from idc_models_tpu.models.lm import attention_lm
from idc_models_tpu.observe import Tracer, trace
from idc_models_tpu.serve import LMServer, Request


@pytest.fixture()
def tracer():
    tr = Tracer()
    prev = trace.set_tracer(tr)
    yield tr
    trace.set_tracer(prev)


@pytest.fixture(params=["untraced", "traced"])
def maybe_tracer(request):
    """Each pipeline contract holds with and without a tracer."""
    if request.param == "untraced":
        assert trace.get_tracer() is None
        yield None
        return
    tr = Tracer()
    prev = trace.set_tracer(tr)
    yield tr
    trace.set_tracer(prev)


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r["name"], []).append(r)
    return out


def _inside(child, parent, slack_ms=1e-3):
    return (parent["t_ms"] <= child["t_ms"] + slack_ms
            and child["t_ms"] + child["dur_ms"]
            <= parent["t_ms"] + parent["dur_ms"] + slack_ms)


# -- tracer ----------------------------------------------------------------


def test_span_parent_names_the_cause_of_an_outermost_span(tracer):
    """`parent=` is taken on an empty stack (a worker thread's first
    span) and ignored under an open span, which parents as before."""
    with trace.span("cause") as cause:
        seen = {}

        def work():
            seen["before"] = trace.current_span_id()
            with trace.span("worker.outer", parent=cause.span_id) as o:
                seen["inside"] = trace.current_span_id()
                seen["outer"] = o.span_id
                with trace.span("worker.inner", parent=cause.span_id):
                    pass

        t = threading.Thread(target=work, name="test-worker")
        t.start()
        t.join()
        assert trace.current_span_id() == cause.span_id
    assert trace.current_span_id() is None
    assert seen["before"] is None and seen["inside"] == seen["outer"]
    recs = _by_name(tracer.records())
    assert recs["worker.outer"][0]["parent"] == cause.span_id
    assert recs["worker.inner"][0]["parent"] == seen["outer"]
    assert recs["cause"][0]["parent"] is None
    # `parent` is the tracer's argument, never an attribute
    assert recs["worker.outer"][0]["attrs"] == {}


def test_current_span_id_is_none_without_a_tracer():
    assert trace.get_tracer() is None
    assert trace.current_span_id() is None
    # the disabled handle takes the argument and stays the shared one
    assert trace.span("x", parent=7) is trace.span("y")


def test_thread_name_in_records_and_chrome_export(tracer, tmp_path):
    def work():
        with trace.span("on.worker"):
            pass
        trace.start_span("detached.on.worker").close()

    t = threading.Thread(target=work, name="named-worker")
    t.start()
    t.join()
    with trace.span("on.main"):
        pass
    recs = _by_name(tracer.records())
    assert recs["on.worker"][0]["thread"] == "named-worker"
    assert recs["detached.on.worker"][0]["thread"] == "named-worker"
    main = recs["on.main"][0]
    assert main["thread"] == threading.current_thread().name
    assert main["tid"] == threading.get_ident()
    doc = json.load(open(tracer.export_chrome(tmp_path / "t.json")))
    names = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "named-worker" in names[xs["on.worker"]["tid"]].split("/")
    assert main["thread"] in names[xs["on.main"]["tid"]].split("/")


# -- input pipeline --------------------------------------------------------


def _loader(n=64, batch=8):
    imgs, labels = synthetic.make_idc_like(n, size=8, seed=0)
    return Loader(ArrayDataset(imgs, labels), batch, shuffle=False)


def test_prefetch_spans_by_name_thread_and_parent(devices, tracer):
    mesh = meshlib.data_mesh(8)
    ld = _loader()
    with trace.span("train.epoch") as ep:
        got = list(pipeline.prefetch_to_mesh(iter(ld), mesh))
    assert len(got) == len(ld) == 8
    deadline = time.time() + 5          # the watcher closes the last one
    while (time.time() < deadline and sum(
            r["name"] == "data.transfer" for r in tracer.records()) < 8):
        time.sleep(0.01)
    recs = _by_name(tracer.records())
    epoch = recs["train.epoch"][0]
    me = threading.current_thread().name
    # 8 batches + the end marker on either side of the queue
    assert len(recs["data.wait"]) == 9 and len(recs["data.full"]) == 9
    assert len(recs["data.load"]) == 9      # the 9th finds the end
    assert len(recs["data.put"]) == len(recs["data.transfer"]) == 8
    for name, thread in (("data.wait", me), ("data.load", "idc-prefetch"),
                         ("data.put", "idc-prefetch"),
                         ("data.full", "idc-prefetch"),
                         ("data.transfer", "idc-prefetch")):
        for r in recs[name]:
            assert r["parent"] == ep.span_id, (name, r)
            assert r["thread"] == thread, (name, r)
    for name in ("data.wait", "data.load", "data.put", "data.full"):
        assert all(_inside(r, epoch) for r in recs[name]), name
    assert all(0 <= r["attrs"]["depth"] <= 2 for r in recs["data.wait"])
    assert [r["attrs"]["index"] for r in recs["data.load"]] == list(range(9))
    nbytes = sum(a.nbytes for a in next(ld.epoch(0)))
    puts = recs["data.put"]
    moves = sorted(recs["data.transfer"], key=lambda r: r["attrs"]["index"])
    assert [m["attrs"]["index"] for m in moves] == list(range(8))
    for p, m in zip(puts, moves):
        assert p["attrs"]["bytes"] == m["attrs"]["bytes"] == nbytes
        # opened before its put's dispatch, closed after the put returned
        assert m["t_ms"] <= p["t_ms"] + 1e-3
        assert m["t_ms"] + m["dur_ms"] >= p["t_ms"] + p["dur_ms"] - 1e-3


def test_recycle_span_once_per_batch_under_the_consumers_span(devices,
                                                              tracer):
    """`data.recycle`: the producer's acquisition of a ring slot, one a
    batch (none for the end marker), parented like `data.load`; the
    second epoch's hold the wait for the first epoch's transfers."""
    mesh = meshlib.data_mesh(8)
    ld = _loader()
    for epoch in range(2):
        with trace.span("train.epoch") as ep:
            assert len(list(pipeline.prefetch_to_mesh(ld.epoch(epoch),
                                                      mesh))) == 8
        recs = _by_name(tracer.records())
        mine = [r for r in recs["data.recycle"] if r["parent"] == ep.span_id]
        loads = [r for r in recs["data.load"] if r["parent"] == ep.span_id]
        assert len(mine) == 8 and len(loads) == 9
        assert len(recs["data.recycle"]) == 8 * (epoch + 1)
        for r, load in zip(mine, loads):
            assert r["thread"] == "idc-prefetch"
            assert _inside(r, recs["train.epoch"][epoch])
            # acquired first, then filled: not inside the load
            assert r["t_ms"] + r["dur_ms"] <= load["t_ms"] + 1e-3


def test_no_recycle_span_without_a_ring(devices, tracer, tmp_path):
    """Iterated directly, wrapped in a generator, or a `FileStream`: the
    batches are the caller's or the stream's own, and nothing opens
    `data.recycle`."""
    from PIL import Image

    mesh = meshlib.data_mesh(8)
    ld = _loader()
    with trace.span("train.epoch"):
        assert len(list(ld.epoch(0))) == 8
        assert len(list(pipeline.prefetch_to_mesh(
            (b for b in ld.epoch(0)), mesh))) == 8
    pairs = []
    for i in range(16):
        path = tmp_path / f"p{i}.png"
        Image.fromarray(np.full((8, 8, 3), i, np.uint8)).save(path)
        pairs.append((str(path), i % 2))
    stream = pipeline.FileStream(pairs, 8, 8, shuffle=False, workers=2)
    try:
        with trace.span("train.epoch"):
            assert len(list(pipeline.prefetch_to_mesh(stream.epoch(0),
                                                      mesh))) == 2
    finally:
        stream.close()
    recs = _by_name(tracer.records())
    assert len(recs["data.load"]) == 9 + 3
    assert "data.recycle" not in recs
    assert ld._ring is None


def test_prefetch_untraced_starts_one_thread_and_allocates_no_span(
        devices, monkeypatch):
    """With no tracer: one thread (no watcher), and every span site
    gets the shared no-op handle — nothing is allocated."""
    assert trace.get_tracer() is None
    started = []
    real_start = threading.Thread.start

    def counting_start(self):
        started.append(self.name)
        real_start(self)

    monkeypatch.setattr(threading.Thread, "start", counting_start)
    made = []
    real_init = trace.Span.__init__
    monkeypatch.setattr(
        trace.Span, "__init__",
        lambda self, *a, **k: (made.append(a[1]), real_init(self, *a, **k))[1])
    mesh = meshlib.data_mesh(8)
    got = list(pipeline.prefetch_to_mesh(iter(_loader()), mesh))
    assert len(got) == 8
    assert started == ["idc-prefetch"]
    assert made == []


def test_prefetch_yields_the_same_batches_traced_and_untraced(devices):
    mesh = meshlib.data_mesh(8)
    ld = _loader()
    plain = [(np.asarray(x), np.asarray(y))
             for x, y in pipeline.prefetch_to_mesh(iter(ld), mesh)]
    tr = Tracer()
    prev = trace.set_tracer(tr)
    try:
        traced = [(np.asarray(x), np.asarray(y))
                  for x, y in pipeline.prefetch_to_mesh(iter(ld), mesh)]
    finally:
        trace.set_tracer(prev)
    want = list(ld.epoch(0))
    assert len(plain) == len(traced) == len(want)
    for (x, y), (tx, ty), (wx, wy) in zip(plain, traced, want):
        np.testing.assert_array_equal(x, wx.astype(x.dtype))
        np.testing.assert_array_equal(tx, x)
        np.testing.assert_array_equal(y, wy)
        np.testing.assert_array_equal(ty, y)


def test_prefetch_abandoned_iterator_stops_every_thread(devices,
                                                       maybe_tracer):
    """tests/test_data.py's contract, for the watcher thread too."""
    mesh = meshlib.data_mesh(8)
    n_before = threading.active_count()
    it = pipeline.prefetch_to_mesh(iter(_loader()), mesh, prefetch=1)
    next(it)
    if maybe_tracer is not None:
        assert "idc-prefetch-watch" in {t.name for t in threading.enumerate()}
    it.close()  # abandon early
    ours = {"idc-prefetch", "idc-prefetch-watch"}
    # wait on the pipeline's OWN threads by name: a count alone can be
    # satisfied by an earlier test's thread ending while the watcher is
    # still on its way out (seen under xdist load)
    for _ in range(50):
        if not ours & {t.name for t in threading.enumerate()}:
            break
        time.sleep(0.1)
    assert not ours & {t.name for t in threading.enumerate()}
    assert threading.active_count() <= n_before


def test_prefetch_propagates_errors_traced_and_untraced(devices,
                                                        maybe_tracer):
    mesh = meshlib.data_mesh(8)

    def bad():
        yield from _loader(16).epoch(0)
        raise ValueError("boom")

    it = pipeline.prefetch_to_mesh(bad(), mesh)
    assert len([next(it), next(it)]) == 2
    with pytest.raises(ValueError, match="boom"):
        next(it)
    if maybe_tracer is not None:
        loads = _by_name(maybe_tracer.records())["data.load"]
        assert loads[-1]["attrs"]["error"] == "ValueError"


def test_fit_epoch_holds_the_data_spans(devices, tracer):
    """Through `fit`: every `data.*` span hangs under a `train.epoch`,
    and `train.step` no longer holds the wait for the batch."""
    from idc_models_tpu.models import small_cnn
    from idc_models_tpu.train import create_train_state, fit, rmsprop
    from idc_models_tpu.train.losses import binary_cross_entropy

    imgs, labels = synthetic.make_idc_like(32, size=10, seed=0)
    model, opt = small_cnn(10, 3, 1), rmsprop(1e-3)
    fit(model=model, optimizer=opt, loss_fn=binary_cross_entropy,
        state=create_train_state(model, opt, jax.random.key(0)),
        train_ds=ArrayDataset(imgs, labels),
        val_ds=ArrayDataset(imgs[:8], labels[:8]), mesh=meshlib.data_mesh(8),
        epochs=2, batch_size=8, verbose=False)
    recs = _by_name(tracer.records())
    loops = {r["id"]: r["name"]
             for r in recs["train.epoch"] + recs["train.eval"]}
    assert len(recs["train.epoch"]) == 2
    for name in ("data.wait", "data.load", "data.put", "data.full"):
        assert recs[name], name
        assert {r["parent"] for r in recs[name]} <= set(loops), name
    # both loops feed from the pipeline: the epoch's and the evaluator's
    assert {loops[r["parent"]] for r in recs["data.wait"]} == {
        "train.epoch", "train.eval"}
    per_epoch = [r for r in recs["data.wait"]
                 if r["parent"] == recs["train.epoch"][0]["id"]]
    assert len(per_epoch) == 4 + 1          # 4 batches and the end marker
    assert len(recs["train.step"]) == 8


# -- serve -----------------------------------------------------------------

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _server(params, **kw):
    return LMServer(params, embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                    t_max=SEQ, mesh=None, cache_dtype=jnp.float32, **kw)


def _covering_tick(recs, span):
    """The `serve.tick` record that `span` names as its parent."""
    return next(t for t in recs["serve.tick"] if t["id"] == span["parent"])


@pytest.mark.parametrize("chunk", [None, 4], ids=["monolithic", "chunked"])
def test_turnaround_spans_one_cycle(devices, params, tracer, chunk):
    """Collect's return to the next dispatch's return: under the tick,
    holding that tick's `serve.refill`, and one per collected window."""
    server = _server(params, n_slots=2, window=4, prefill_chunk=chunk)
    for i, n in enumerate((3, 9, 5)):
        assert server.submit(Request(id=f"r{i}", prompt=tuple(range(1, n + 1)),
                                     max_new_tokens=6))
    server.drain()
    assert all(server.poll(f"r{i}").status == "ok" for i in range(3))
    recs = _by_name(tracer.records())
    assert "serve.admit" in recs and "serve.refill" in recs
    assert all("refill" not in r["attrs"] for r in recs["serve.admit"])
    turns = recs["serve.turnaround"]
    collected = [c for c in recs["serve.collect"] if c["attrs"]["slots"]]
    assert len(turns) == len(collected) > 0
    assert any(t["attrs"]["dispatched"] for t in turns)
    # the last window's collect leaves nothing to run: the no-dispatch
    # path closes its turnaround all the same, once
    assert [t["attrs"]["dispatched"] for t in turns].count(False) >= 1
    assert len({t["id"] for t in turns}) == len(turns)
    for t in turns:
        tick = _covering_tick(recs, t)
        assert _inside(t, tick)
        assert set(t["attrs"]) == {"slots", "dispatched", "admitted"}
        refills = [r for r in recs["serve.refill"] if r["parent"] == tick["id"]]
        assert len(refills) == 1 and _inside(refills[0], t)
        assert t["attrs"]["admitted"] == refills[0]["attrs"]["admitted"]
        wins = [w for w in recs.get("serve.window", [])
                if w["parent"] == tick["id"]]
        assert len(wins) == (1 if t["attrs"]["dispatched"] else 0)
        assert all(_inside(w, t) for w in wins)
        # it starts where the collect ends
        coll = next(c for c in recs["serve.collect"]
                    if c["parent"] == tick["id"])
        assert t["t_ms"] >= coll["t_ms"]
        assert t["t_ms"] <= coll["t_ms"] + coll["dur_ms"] + 1e-3
    # admission spans carry the request's id, whichever pass ran them
    rids = {f"r{i}" for i in range(3)}
    assert {r["attrs"]["rid"] for r in recs["serve.insert"]} == rids
    passes = {"serve.admit", "serve.refill"}
    by_id = {r["id"]: r for rs in recs.values() for r in rs}
    if chunk is None:
        assert "serve.start_prefill" not in recs
        assert {by_id[r["parent"]]["name"] for r in recs["serve.insert"]} == {
            "serve.prefill"}
    else:
        starts = recs["serve.start_prefill"]
        assert {r["attrs"]["rid"] for r in starts} == rids
        assert all(isinstance(r["attrs"]["slot"], int) for r in starts)
        assert {by_id[r["parent"]]["name"] for r in starts} <= passes
        assert {by_id[r["parent"]]["name"]
                for r in recs["serve.insert"]} == {"serve.admit"}


@pytest.mark.parametrize("site", ["begin_window", "refill"])
def test_turnaround_closes_once_on_an_engine_failure(devices, params, tracer,
                                                     site):
    server = _server(params, n_slots=2, window=4, eos_id=None)
    assert server.submit(Request(id="a", prompt=(1, 2, 3),
                                 max_new_tokens=12))
    server.step()                     # admit a; window in flight
    server.step()                     # collect it; next window in flight

    def boom(*a, **k):
        raise RuntimeError("device fell off the bus")

    if site == "begin_window":
        server.engine.begin_window = boom
    else:
        passes, real = [], server.scheduler._admit_free_slots

        def second_pass_fails():
            passes.append(1)
            return real() if len(passes) == 1 else boom()

        server.scheduler._admit_free_slots = second_pass_fails
    n_before = len(_by_name(tracer.records()).get("serve.turnaround", []))
    with pytest.raises(RuntimeError, match="fell off the bus"):
        server.step()
    turns = _by_name(tracer.records())["serve.turnaround"]
    assert len(turns) == n_before + 1
    last = max(turns, key=lambda r: r["t_ms"])
    assert last["attrs"]["dispatched"] is False
    assert last["attrs"]["error"] == "RuntimeError"
    assert _inside(last, _covering_tick(_by_name(tracer.records()), last))


def test_no_turnaround_without_a_collected_window(devices, params, tracer):
    """A tick that collected nothing is not between two windows."""
    server = _server(params, n_slots=2, window=4)
    server.step()                               # empty server
    assert server.submit(Request(id="a", prompt=(1, 2, 3),
                                 max_new_tokens=4))
    server.step()                               # admits, first dispatch
    recs = _by_name(tracer.records())
    assert len(recs["serve.tick"]) == 2 and "serve.window" in recs
    assert "serve.turnaround" not in recs
