"""ISSUE 18: the elastic cluster — autoscaling from health documents
(serve/cluster/autoscaler.py), warm replica spin-up through the
persistent compile cache (serve/compile_cache.py), and graceful drain
with live mid-decode slot migration — against its hard contracts:

1. POLICY — `decide()` is pure over (healths, now, state, cfg): dwell
   hysteresis, post-action cooldown, and min/max bounds all replay
   deterministically from a fake clock; holds are silent.
2. WARM SPIN-UP — the compile cache round-trips an AOT-serialized
   executable; a corrupt blob is evicted and reported as a miss (never
   a crash); any toolchain/config drift changes the key; a second
   replica built against a populated cache deserializes instead of
   compiling.
3. MIGRATION — draining with migrate=True moves a MID-DECODE request's
   slot (KV rows + RNG key-data + emitted tokens) onto a peer and the
   final output is bit-identical to an unmigrated run, greedy and
   sampled; with no free peer slot it falls back to journal-style
   from-the-prompt re-placement, still bit-identical; a crash in the
   export->import gap loses nothing — the source WAL still holds the
   request and replay reproduces it exactly.
4. HONESTY — with every decode replica draining or dead, submit()
   returns the terminal shed Result naming the condition instead of
   queueing into a fleet that will never run it; add_replica revives
   the cluster and the same request then succeeds.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from idc_models_tpu.models.lm import Generator, attention_lm
from idc_models_tpu.serve import (
    AutoscaleConfig, Autoscaler, CompileCache, Request, Router,
    build_replica,
)
from idc_models_tpu.serve.cluster import autoscaler as asc

VOCAB, SEQ, E, HEADS, MLP, BLOCKS = 11, 32, 32, 2, 64, 2
DEV0 = jax.devices()[:1]   # where an unsharded jit compiles and runs


@pytest.fixture(scope="module")
def params():
    model = attention_lm(VOCAB, SEQ, embed_dim=E, num_heads=HEADS,
                         mlp_dim=MLP, num_blocks=BLOCKS)
    return model.init(jax.random.key(0)).params


def _model_kw():
    return dict(embed_dim=E, num_heads=HEADS, num_blocks=BLOCKS,
                t_max=SEQ)


def _replica(params, rid, *, device=None, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("window", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    return build_replica(params, replica_id=rid, device=device,
                         **_model_kw(), **kw)


def _serial_tokens(params, prompt, steps):
    gen = Generator(params, mesh=None, cache_dtype=jnp.float32,
                    **_model_kw())
    logits, caches = gen.prefill(jnp.asarray([prompt], jnp.int32))
    toks, _, _ = gen.decode(caches, logits, len(prompt), steps)
    return toks.tolist()[0]


def _health(qd=0, load=0, *, shedding=False, burning=False,
            pages=(None, None), state="live", role="mixed"):
    return {"state": state, "role": role, "queue_depth": qd,
            "load": load, "shedding": shedding, "slo_breached": burning,
            "kv_pages_total": pages[0], "kv_pages_used": pages[1]}


# -- 1. autoscaling policy --------------------------------------------------


def test_autoscale_config_validation():
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscaleConfig(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="hysteresis"):
        AutoscaleConfig(queue_low=4.0, queue_high=4.0)
    with pytest.raises(ValueError, match="page_headroom"):
        AutoscaleConfig(page_headroom=1.0)
    with pytest.raises(ValueError, match="dwell_s"):
        AutoscaleConfig(dwell_s=-1.0)


def test_autoscale_dwell_gates_the_up_signal():
    """One bursty tick never buys a replica: the up signal must HOLD
    for dwell_s, and quiet in between resets the clock."""
    cfg = AutoscaleConfig(queue_high=4.0, dwell_s=1.0, cooldown_s=0.0)
    hot = [_health(qd=10)]
    a, _, st = asc.decide(hot, now=0.0, cfg=cfg)
    assert a == "hold"                     # signal just appeared
    a, _, st = asc.decide(hot, now=0.5, state=st, cfg=cfg)
    assert a == "hold"                     # held 0.5 < dwell 1.0
    # a quiet tick resets the dwell clock...
    a, _, st = asc.decide([_health(qd=2)], now=0.8, state=st, cfg=cfg)
    assert a == "hold" and st["up_since"] is None
    # ...so the signal must re-earn the full dwell
    a, _, st = asc.decide(hot, now=1.0, state=st, cfg=cfg)
    assert a == "hold"
    a, reason, st = asc.decide(hot, now=2.1, state=st, cfg=cfg)
    assert a == "up" and "queue_high" in reason


def test_autoscale_cooldown_prevents_staircasing():
    """After an action the policy is quiet for cooldown_s even though
    the raw signal persists through spin-up — without this the fleet
    staircases straight to max."""
    cfg = AutoscaleConfig(queue_high=4.0, dwell_s=0.0, cooldown_s=5.0)
    hot = [_health(qd=10)]
    a, _, st = asc.decide(hot, now=0.0, cfg=cfg)
    assert a == "up"
    a, reason, st = asc.decide(hot, now=2.0, state=st, cfg=cfg)
    assert (a, reason) == ("hold", "cooldown")
    a, _, st = asc.decide(hot, now=5.5, state=st, cfg=cfg)
    assert a == "up"                       # cooldown elapsed


def test_autoscale_bounds_and_down_signal():
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=2,
                          queue_low=1.0, queue_high=4.0,
                          dwell_s=0.0, cooldown_s=0.0)
    # at max: the up signal reports the bound instead of firing
    a, reason, _ = asc.decide([_health(qd=10), _health(qd=10)],
                              now=0.0, cfg=cfg)
    assert a == "hold" and "max_replicas" in reason
    # two idle replicas above min: down fires
    a, reason, _ = asc.decide([_health(qd=0), _health(qd=0)],
                              now=0.0, cfg=cfg)
    assert a == "down" and "queue_low" in reason
    # at min: never below the floor
    a, _, _ = asc.decide([_health(qd=0)], now=0.0, cfg=cfg)
    assert a == "hold"


def test_autoscale_down_blocked_by_shed_or_burn():
    """An idle-looking queue does not license scale-down while any
    replica sheds or burns its SLO — load is hiding, not absent."""
    cfg = AutoscaleConfig(dwell_s=0.0, cooldown_s=0.0)
    for sick in (_health(qd=0, shedding=True),
                 _health(qd=0, burning=True)):
        a, _, _ = asc.decide([_health(qd=0), sick], now=0.0, cfg=cfg)
        assert a != "down"                 # shedding even argues UP
    # shedding is itself an UP signal regardless of queue depth
    a, reason, _ = asc.decide([_health(qd=0, shedding=True)],
                              now=0.0, cfg=cfg)
    assert a == "up" and "shedding" in reason


def test_autoscale_page_headroom_and_liveness_filters():
    cfg = AutoscaleConfig(page_headroom=0.2, dwell_s=0.0,
                          cooldown_s=0.0)
    a, reason, _ = asc.decide([_health(qd=0, pages=(100, 95))],
                              now=0.0, cfg=cfg)
    assert a == "up" and "headroom" in reason
    # draining/dead/prefill replicas neither vote nor count as capacity
    fleet = [_health(qd=50, state="draining"),
             _health(qd=50, state="dead"),
             _health(qd=50, role="prefill")]
    a, reason, st = asc.decide(fleet, now=0.0, cfg=cfg)
    assert (a, reason) == ("hold", "no live decode replica")
    assert st == asc._fresh_state()


def test_autoscaler_wrapper_records_actions_only():
    auto = Autoscaler(AutoscaleConfig(dwell_s=0.0, cooldown_s=0.0))
    assert auto.evaluate([_health(qd=2)], now=0.0) is None   # hold
    rec = auto.evaluate([_health(qd=10)], now=1.0)
    assert rec is not None and rec["action"] == "up"
    assert rec["live"] == 1 and rec["t"] == 1.0
    assert [d["action"] for d in auto.decisions] == ["up"]


# -- 2. compile cache + warm spin-up ----------------------------------------


def test_compile_cache_roundtrip_and_key_drift(tmp_path):
    """Store an AOT-compiled executable, reopen the cache cold, load
    it back, and run BOTH: identical outputs. Any drift in program
    name or fingerprint is a different key."""
    f = jax.jit(lambda x: x * 2 + 1)
    lowered = f.lower(jnp.zeros((4,), jnp.float32))
    cc = CompileCache(tmp_path)
    key = cc.key(program="probe", fingerprint={"embed": E})
    assert cc.load(key, devices=DEV0) is None and cc.misses == 1
    exe = cc.compile_and_store(key, lowered)
    assert cc.stores == 1 and cc.compile_s > 0
    # a fresh instance (the "new process") deserializes the same key
    cc2 = CompileCache(tmp_path)
    warm = cc2.load(key, devices=DEV0)
    assert warm is not None
    assert cc2.summary()["hits"] == 1 and cc2.deserialize_s > 0
    x = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(exe(x)),
                                  np.asarray(warm(x)))
    # invalidation IS the key: program or fingerprint drift never
    # collides with the stored entry
    assert cc.key(program="other", fingerprint={"embed": E}) != key
    assert cc.key(program="probe", fingerprint={"embed": E + 1}) != key


def test_compile_cache_corrupt_blob_evicted_as_miss(tmp_path):
    """A torn/foreign blob under a valid key is evicted and counted
    as a miss — spin-up falls back to a real compile, never dies on a
    bad cache entry, and the rebuilt entry replaces it."""
    cc = CompileCache(tmp_path)
    key = cc.key(program="probe", fingerprint={})
    blob = cc._file(key)
    blob.write_bytes(b"not a serialized executable")
    assert cc.load(key, devices=DEV0) is None
    assert cc.evicted_corrupt == 1 and cc.misses == 1
    assert not blob.exists()               # evicted, not left to rot
    f = jax.jit(lambda x: x + 1)
    cc.compile_and_store(key, f.lower(jnp.zeros((2,), jnp.float32)))
    assert CompileCache(tmp_path).load(key, devices=DEV0) is not None


def test_warm_replica_spinup_hits_cache(params, tmp_path):
    """The ISSUE's warm spin-up contract at the replica surface: the
    first build compiles and stores, a second replica against the same
    populated cache deserializes (hits > 0, zero new stores) and still
    serves bit-identically."""
    cache = CompileCache(tmp_path / "cc")
    r0 = _replica(params, "r0", compile_cache=cache)
    assert cache.stores > 0 and cache.hits == 0
    stored = cache.stores
    r1 = _replica(params, "r1", compile_cache=cache)
    assert cache.hits > 0, "warm spin-up must deserialize, not compile"
    assert cache.stores == stored
    router = Router([r0, r1])
    q = Request(id="warm", prompt=(1, 2, 3, 4), max_new_tokens=6)
    out = router.run([(0.0, q)])
    assert out[0].status == "ok"
    assert out[0].tokens == _serial_tokens(params, q.prompt, 6)
    router.close()


def test_stored_blobs_are_compiled_afresh(tmp_path):
    """A blob is never made from an executable that jax took out of its
    OWN persistent cache, nor is its compile put there: serialized
    again, an executable loaded from that cache loses its standalone
    kernels on jaxlib's CPU backend, and in a process that has compiled
    no kernel of the name itself the blob loads and then dies at its
    first dispatch ("Function wrapped_iota not found": three tests of
    this file, whenever a loaded machine took over the half second the
    suite admits a compile to jax's cache at). So: with EVERY compile
    admitted, the store's compile leaves no entry in jax's cache, runs
    with that cache off, and leaves it on for everyone else."""
    import pathlib

    def afresh_probe(x):
        return x * 3 + 1

    lowered = jax.jit(afresh_probe).lower(jnp.zeros((3,), jnp.float32))
    during = []

    class Spy:
        def compile(self):
            during.append(jax.config.jax_enable_compilation_cache)
            return lowered.compile()

    jax_cache = pathlib.Path(jax.config.jax_compilation_cache_dir)
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        cc = CompileCache(tmp_path)
        key = cc.key(program="afresh_probe", fingerprint={})
        exe = cc.compile_and_store(key, Spy())
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    assert during == [False] and jax.config.jax_enable_compilation_cache
    assert not list(jax_cache.glob("*afresh_probe*"))
    np.testing.assert_array_equal(
        np.asarray(exe(jnp.ones((3,), jnp.float32))), 4.0)
    assert CompileCache(tmp_path).load(key, devices=DEV0) is not None


# -- 3. live slot migration -------------------------------------------------


def _journal_events(path):
    out = []
    for line in path.read_text().splitlines():
        rec = json.loads(line)
        out.append((rec.get("event"), rec.get("id"),
                    rec.get("status"), rec.get("direction")))
    return out


def test_drain_migrates_live_slots_bit_identical(devices, params,
                                                 tmp_path):
    """The tentpole drill: two requests mid-decode on two replicas,
    drain r0 with migrate=True. r0's request moves IN ITS SLOT (KV +
    RNG + emitted tokens) onto r1 and finishes there with output
    bit-identical to the serial oracle; both WALs carry the gap
    protocol (out+migrated on the source, submit+in+ok on the
    target)."""
    reps = [_replica(params, f"r{i}", device=devices[i],
                     journal_path=str(tmp_path / f"j{i}.jsonl"))
            for i in range(2)]
    router = Router(reps)
    rng = np.random.default_rng(3)
    reqs = [Request(id=f"m{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 4 + i)),
                    max_new_tokens=12)
            for i in range(2)]
    for q in reqs:
        assert router.submit(q)
    assert router._owner["m0"].replica_id == "r0"
    router.step()                          # both now MID-decode
    moved = router.drain_replica("r0", migrate=True)
    assert "m0" in moved
    assert [m["rid"] for m in router.slot_migrations] == ["m0"]
    assert router.slot_migrations[0]["to"] == "r1"
    router.drain()
    for q in reqs:
        got = router.poll(q.id)
        assert got is not None and got.status == "ok", (q.id, got)
        assert got.tokens == _serial_tokens(params, q.prompt, 12), q.id
    assert router.summary()["cluster_slot_migrations"] == 1
    src = _journal_events(tmp_path / "j0.jsonl")
    tgt = _journal_events(tmp_path / "j1.jsonl")
    assert ("journal_migrate", "m0", None, "out") in src
    assert ("journal_finish", "m0", "migrated", None) in src
    assert ("journal_submit", "m0", None, None) in tgt
    assert ("journal_migrate", "m0", None, "in") in tgt
    assert ("journal_finish", "m0", "ok", None) in tgt


def test_sampled_migration_carries_rng_bit_identical(devices, params):
    """Sampled decode across a migration: the request's raw threefry
    key-data rides the slot move, so the migrated run reproduces the
    unmigrated run bit for bit even though it lands in a DIFFERENT
    slot index on the peer."""
    def fleet():
        return [_replica(params, f"r{i}", device=devices[i],
                         temperature=1.0)
                for i in range(2)]

    q = Request(id="s0", prompt=(1, 2, 3, 4, 5), max_new_tokens=10,
                seed=42)
    peer_load = Request(id="s1", prompt=(6, 7, 8), max_new_tokens=10,
                        seed=7)
    # oracle: the same pair, same placement, NO migration
    r_static = Router(fleet())
    for p in (q, peer_load):
        assert r_static.submit(p)
    r_static.drain()
    want = r_static.poll("s0").tokens
    r_static.close()

    r_mig = Router(fleet())
    for p in (q, peer_load):
        assert r_mig.submit(p)
    r_mig.step()
    moved = r_mig.drain_replica("r0", migrate=True)
    assert "s0" in moved and r_mig.slot_migrations
    r_mig.drain()
    got = r_mig.poll("s0")
    assert got.status == "ok" and got.tokens == want
    r_mig.close()


def test_migration_falls_back_when_no_free_slot(devices, params):
    """With every peer slot occupied, drain migrate=True falls back to
    journal-style from-the-prompt re-placement — slower, still
    bit-identical, and the rollup tells the two modes apart."""
    reps = [_replica(params, f"r{i}", device=devices[i], n_slots=1)
            for i in range(2)]
    router = Router(reps)
    reqs = [Request(id=f"f{i}", prompt=(1 + i, 2 + i, 3 + i),
                    max_new_tokens=10)
            for i in range(2)]
    for q in reqs:
        assert router.submit(q)
    router.step()                          # r1's only slot is busy
    moved = router.drain_replica("r0", migrate=True)
    assert "f0" in moved
    assert router.slot_migrations == []    # no seat -> no slot move
    router.drain()
    for q in reqs:
        got = router.poll(q.id)
        assert got.status == "ok"
        assert got.tokens == _serial_tokens(params, q.prompt, 10), q.id
    s = router.summary()
    assert s["cluster_slot_migrations"] == 0
    assert s["cluster_migrations"] >= 1    # the fallback path


def test_crash_in_export_import_gap_loses_nothing(devices, params,
                                                  tmp_path):
    """The gap protocol: the source WAL keeps the request OPEN until
    the import lands. Killing the source after export_running but
    before any import leaves the WAL's pending set intact, and the
    journal failover replays the request from the prompt,
    bit-identically."""
    reps = [_replica(params, f"r{i}", device=devices[i],
                     journal_path=str(tmp_path / f"j{i}.jsonl"))
            for i in range(2)]
    router = Router(reps)
    q = Request(id="gap0", prompt=(1, 2, 3, 4), max_new_tokens=10)
    assert router.submit(q)
    assert router._owner["gap0"].replica_id == "r0"
    router.step()
    # reach into the drain protocol mid-flight: quiesce, then export —
    # and then the source dies before anyone imports
    src = reps[0].server
    src.quiesce()
    src.scheduler.begin_drain()
    entry, snap = src.scheduler.export_running("gap0")
    assert entry.rid == "gap0" and snap is not None
    migrated = router.kill_replica("r0")
    assert "gap0" in migrated              # WAL still held it open
    router.drain()
    got = router.poll("gap0")
    assert got is not None and got.status == "ok"
    assert got.tokens == _serial_tokens(params, q.prompt, 10)
    # the dead source's WAL must NOT claim the request finished
    src_events = _journal_events(tmp_path / "j0.jsonl")
    assert not any(e == "journal_finish" and r == "gap0"
                   for e, r, _, _ in src_events)


# -- 4. all-draining honesty + revival --------------------------------------


def test_all_draining_sheds_honestly_then_add_replica_revives(
        devices, params):
    """Every decode replica draining => submit() answers with the
    terminal shed Result naming the condition (not a queue into a
    fleet that will never run it). add_replica revives the cluster
    and the SAME request then succeeds."""
    reps = [_replica(params, f"r{i}") for i in range(2)]
    router = Router(reps)
    for rid in ("r0", "r1"):
        router.drain_replica(rid, wait=True)
    q = Request(id="orphan", prompt=(1, 2, 3), max_new_tokens=4)
    assert router.submit(q) is False
    got = router.poll("orphan")
    assert got is not None and got.status == "shed"
    assert "no live decode-capable replica" in got.error
    assert router.summary()["cluster_shed"] >= 1
    # revival: a fresh replica joins and the same request now runs
    router.add_replica(_replica(params, "r2"))
    assert router.summary()["cluster_replicas_live"] == 1
    assert router.submit(q)
    router.drain()
    final = router.poll("orphan")
    assert final.status == "ok"
    assert final.tokens == _serial_tokens(params, q.prompt, 4)


def test_add_replica_rejects_duplicate_id(devices, params):
    router = Router([_replica(params, "r0")])
    with pytest.raises(ValueError, match="already in the fleet"):
        router.add_replica(_replica(params, "r0"))


# -- 5. the elastic loop end to end -----------------------------------------


@pytest.mark.parametrize("cached", [False, True],
                         ids=["no_cache", "shared_compile_cache"])
def test_router_autoscales_up_then_down_with_fake_clock(devices, params,
                                                        tmp_path, cached):
    """The full control loop on a deterministic clock: a burst trips
    the up signal (replica_factory builds 'auto0'), the drained queue
    trips the down signal (the least-loaded replica drains WITH
    migration), every request finishes ok, and the fleet lands back at
    min_replicas. Over a shared compile cache the replica spun up
    mid-trace opens WARM: it deserializes what the first replica
    stored and stores nothing (a cache key pins its devices, so the
    warm replica opens on the device the entries were compiled for)."""
    t = [0.0]

    def clock():
        t[0] += 0.25
        return t[0]

    cache = CompileCache(tmp_path / "cc") if cached else None
    built = []

    def factory(rid):
        stored = cache.stores if cached else None
        rep = _replica(params, rid, device=devices[0 if cached else 1],
                       compile_cache=cache)
        if cached:
            assert cache.hits > 0 and cache.stores == stored, (
                cache.summary())
        built.append(rid)
        return rep

    auto = Autoscaler(AutoscaleConfig(
        min_replicas=1, max_replicas=2, queue_high=2.0, queue_low=1.0,
        dwell_s=0.4, cooldown_s=1.0))
    router = Router([_replica(params, "r0", device=devices[0],
                              compile_cache=cache)],
                    clock=clock, autoscaler=auto,
                    replica_factory=factory)
    if cached:
        assert cache.stores > 0 and cache.hits == 0
    rng = np.random.default_rng(13)
    reqs = [Request(id=f"e{i}",
                    prompt=tuple(int(x) for x in
                                 rng.integers(0, VOCAB, 3 + i % 4)),
                    max_new_tokens=6)
            for i in range(8)]
    for q in reqs:
        assert router.submit(q)
    router.drain()
    assert built == ["auto1"]        # ordinal continues the fleet's
    # the drained fleet is idle; keep the control loop ticking so the
    # down signal earns its dwell + cooldown and fires
    for _ in range(16):
        router.step()
    actions = [d["action"] for d in auto.decisions]
    assert actions[0] == "up" and "down" in actions
    for q in reqs:
        got = router.poll(q.id)
        assert got is not None and got.status == "ok", (q.id, got)
        assert got.tokens == _serial_tokens(params, q.prompt, 6), q.id
    s = router.summary()
    assert s["cluster_replicas_live"] == 1     # back at the floor
    assert s["cluster_shed"] == 0
    # no duplicated results: one Result per request id
    ids = [r.id for r in router.results()]
    assert sorted(ids) == sorted(q.id for q in reqs)
    router.close()


def test_cli_serve_cluster_elastic_smoke(devices, capsys, tmp_path):
    """The serve-cluster verb with the elastic flags: autoscaler armed
    and a shared compile cache — epilogue reports both, the summary
    parses, and a SECOND run against the same cache opens warm."""
    from idc_models_tpu.cli import main

    cc_dir = str(tmp_path / "cc")
    argv = [
        "serve-cluster", "--replicas", "1", "--autoscale-max", "2",
        "--vocab", "11", "--t-max", "32", "--embed-dim", "32",
        "--num-heads", "2", "--mlp-dim", "64", "--num-blocks", "2",
        "--slots", "2", "--window", "4", "--requests", "6",
        "--compile-cache", cc_dir]
    assert main(argv) == 0
    out = capsys.readouterr().out
    assert "autoscaler:" in out and "bounds [1, 2]" in out
    assert "-> 1 store(s)" in out
    summary = json.loads(out.split("cluster summary: ", 1)[1]
                         .splitlines()[0])
    assert summary["cluster_requests"] == 6
    assert summary["cluster_shed"] == 0
    assert main(argv) == 0                 # same cache: warm open
    out2 = capsys.readouterr().out
    assert "1 hit(s)" in out2 and "0 miss(es)" in out2


def test_sigterm_handler_unwinds_to_drain():
    """The serve verbs' SIGTERM contract at the mechanism level: armed
    handler raises _DrainRequested in the main thread; disarm restores
    the previous disposition."""
    import os
    import signal

    from idc_models_tpu.cli import (
        _DrainRequested, _arm_sigterm, _disarm_sigterm,
    )

    prev = _arm_sigterm()
    try:
        with pytest.raises(_DrainRequested):
            os.kill(os.getpid(), signal.SIGTERM)
    finally:
        _disarm_sigterm(prev)
    assert signal.getsignal(signal.SIGTERM) == (
        prev if prev is not None else signal.SIG_DFL)


def test_docs_cover_elasticity():
    """Satellite doc gate: the ROBUSTNESS "Elasticity" section and the
    README flags must exist so the elastic layer stays discoverable."""
    from pathlib import Path

    root = Path(__file__).parent.parent
    robust = (root / "docs" / "ROBUSTNESS.md").read_text()
    assert "Elasticity" in robust
    for needle in ("dwell", "cooldown", "compile_cache",
                   "slot migration", "SIGTERM"):
        assert needle in robust, f"docs/ROBUSTNESS.md missing {needle}"
    readme = (root / "README.md").read_text()
    for needle in ("--autoscale-max", "--compile-cache", "SIGTERM"):
        assert needle in readme, f"README.md missing {needle}"
