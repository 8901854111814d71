"""Hierarchical KV cache: host-DRAM/disk spill tiers (ISSUE 19).

The contracts, on one shared tiny f32 paged engine (watched by a
RecompileSentinel at policy='raise' from construction — the spill and
restore paths reuse the handoff extract/inject programs, so every test
below doubles as a zero-new-program-families pin):

* **store units** — HostPageStore LRU under a byte budget with
  demotion to the disk tier; DiskPageStore fixed-record mmap file with
  manifest integrity: a torn/corrupt record is QUARANTINED BY NAME
  (``SpillCorruptEntryError`` in ``quarantine_log``) and reads as a
  miss → recompute, never a crash and never wrong tokens;
* **token identity** — restore-from-spill == recompute-prefill ==
  HBM-hit, on plain, speculative, and chunked-prefill traffic, with
  the eviction that forces the spill happening mid-run;
* **receipts** — spills/restores land in ``ServeMetrics``
  (``pages_spilled``/``pages_restored``/tier hit counters, all in
  ``_WINDOW_COUNTERS``) and publish add/drop entries on
  ``Scheduler.kv_receipts`` — the feed the fleet prefix directory
  drains (tests/test_prefix_directory.py).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtdl_tpu.models.transformer import transformer_lm
from dtdl_tpu.obs import Observer
from dtdl_tpu.serve import (DiskPageStore, HostPageStore, InferenceEngine,
                            NGramDraft, PageAllocator, Request, Scheduler,
                            SpillCorruptEntryError, page_chain_hashes)
from dtdl_tpu.serve.metrics import ServeMetrics

MAX_SEQ = 48
BUCKETS = (8, 16)
PAGE = 8


@pytest.fixture(scope="module")
def model():
    return transformer_lm(
        "tiny", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        d_ff=64, max_seq=MAX_SEQ, attn_impl="dense", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return nn.unbox(model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))["params"])


@pytest.fixture(scope="module")
def obs():
    return Observer(sentinel="raise")


@pytest.fixture(scope="module")
def engine(model, params, obs):
    # pool deliberately tight (5 pages usable): two in-flight requests
    # evict each other's cached prefixes, which is exactly the traffic
    # the spill tier exists for
    return InferenceEngine(model, params, n_slots=2, buckets=BUCKETS,
                           page_size=PAGE, n_pages=6, observer=obs)


@pytest.fixture(scope="module")
def big_engine(model, params, obs):
    # roomy pool: the no-eviction oracle (every prefix stays in HBM)
    return InferenceEngine(model, params, n_slots=2, buckets=BUCKETS,
                           page_size=PAGE, observer=obs)


SYS = list(range(1, 10))          # 9 tokens: one full registered page


def churn(sched, seeds, n_new=3):
    """Distinct-prefix traffic that forces eviction of cached pages."""
    for t in seeds:
        done = sched.run([Request([t] * 9 + [t + 1], n_new)])
        assert done[0].error is None, done[0].error


def payload(seed, shape=(1, 2, 3), scale=True):
    rng = np.random.default_rng(seed)
    out = {"k": {"w": rng.standard_normal(shape).astype(np.float32)},
           "v": {"w": rng.standard_normal(shape).astype(np.float32)}}
    if scale:
        out["k"]["s"] = rng.standard_normal((1, 3)).astype(np.float32)
        out["v"]["s"] = rng.standard_normal((1, 3)).astype(np.float32)
    return out


def same_payload(a, b):
    fa = jax.tree.leaves(a)
    fb = jax.tree.leaves(b)
    return len(fa) == len(fb) and all(
        np.array_equal(x, y) for x, y in zip(fa, fb))


# ---------------------------------------------------------------------------
# chain hashes: the shared address space of cache, stores, and router
# ---------------------------------------------------------------------------

def test_page_chain_hashes_match_allocator():
    toks = list(range(32))
    al = PageAllocator(n_pages=8, page_size=4)
    assert page_chain_hashes(toks, 4) == al.page_hashes(toks)
    # chained: a page's hash covers everything before it
    a = page_chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    b = page_chain_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert a[0] != b[0] and a[1] != b[1]
    assert page_chain_hashes([1, 2, 3], 4) == []      # partial page only


# ---------------------------------------------------------------------------
# host tier (pure host-side unit)
# ---------------------------------------------------------------------------

def test_host_store_lru_within_budget():
    p = payload(0)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(p))
    store = HostPageStore(byte_budget=2 * nbytes)
    store.put(1, payload(1))
    store.put(2, payload(2))
    assert store.holds(1) == "host" and store.holds(2) == "host"
    store.get(1)                    # 2 becomes LRU
    store.put(3, payload(3))        # evicts 2 (no disk tier: dropped)
    assert store.holds(2) is None and store.drops == 1
    assert store.holds(1) == "host" and store.holds(3) == "host"
    assert same_payload(store.get(1), payload(1))
    assert store.get(2) is None
    assert store.spilled_pages == 3 and store.host_hits == 2


def test_host_store_demotes_to_disk_and_promotes_back(tmp_path):
    p = payload(0)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(p))
    dropped = []
    disk = DiskPageStore(str(tmp_path), byte_budget=2 * nbytes)
    store = HostPageStore(byte_budget=nbytes, disk=disk,
                          on_drop=dropped.append)
    store.put(1, payload(1))
    store.put(2, payload(2))        # demotes 1 to disk
    assert store.holds(1) == "disk" and store.holds(2) == "host"
    assert store.demotions == 1 and disk.puts == 1
    got = store.get(1)              # disk hit, promoted back to host
    assert same_payload(got, payload(1))
    assert store.disk_hits == 1 and store.holds(1) == "host"
    # a full cascade: host LRU -> disk LRU -> on_drop receipt from the
    # LAST tier only
    store.put(3, payload(3))
    store.put(4, payload(4))
    store.put(5, payload(5))
    assert dropped, "disk overflow must surface an on_drop receipt"
    assert all(store.holds(h) is None for h in dropped)


# ---------------------------------------------------------------------------
# disk tier: fixed records, manifest, quarantine-by-name
# ---------------------------------------------------------------------------

def test_disk_store_roundtrip_and_manifest(tmp_path):
    disk = DiskPageStore(str(tmp_path))
    assert disk.put(7, payload(7))
    assert disk.put(8, payload(8))
    assert same_payload(disk.get(7), payload(7))
    assert same_payload(disk.get(8), payload(8))
    assert disk.hits == 2 and disk.corrupt_entries == 0
    # geometry is pinned by the first payload: anything else is refused
    assert not disk.put(9, payload(9, shape=(2, 2, 3)))
    import json
    with open(disk.manifest_path) as f:
        man = json.load(f)
    assert set(man["entries"]) == {"7", "8"}
    assert all("sha256" in e for e in man["entries"].values())


def test_corrupt_disk_entry_quarantines_by_name(tmp_path):
    disk = DiskPageStore(str(tmp_path))
    assert disk.put(7, payload(7))
    assert disk.put(8, payload(8))
    slot7 = disk._slots[7]
    # torn write / bit rot: flip one byte of record 7 on the medium
    with open(disk.path, "r+b") as f:
        off = slot7 * disk.record_bytes + 5
        f.seek(off)
        byte = f.read(1)
        f.seek(off)
        f.write(bytes([byte[0] ^ 0xFF]))
    disk._mm.close()                # reopen the mapping over new bytes
    import mmap
    disk._mm = mmap.mmap(disk._fh.fileno(), disk._n_slots
                         * disk.record_bytes)
    # the read MISSES (caller recomputes) instead of crashing or
    # returning wrong bytes, and the event is named in the log
    assert disk.get(7) is None
    assert disk.corrupt_entries == 1
    assert 7 not in disk
    err = disk.quarantine_log[-1]
    assert isinstance(err, SpillCorruptEntryError)
    assert "sha256 mismatch" in str(err) and disk.path in str(err)
    assert err.slot == slot7
    # the suspect slot is never reused; healthy entries are untouched
    assert disk.put(9, payload(9))
    assert disk._slots[9] != slot7
    assert same_payload(disk.get(8), payload(8))
    assert same_payload(disk.get(9), payload(9))


def test_disk_store_lru_eviction_reuses_slots(tmp_path):
    p = payload(0)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(p))
    disk = DiskPageStore(str(tmp_path), byte_budget=2 * nbytes)
    disk.put(1, payload(1))
    disk.put(2, payload(2))
    disk.get(1)                     # 2 is now LRU
    disk.put(3, payload(3))         # evicts 2, reuses its slot
    assert 2 not in disk and disk.drops == 1
    assert disk._n_slots == 2, "freed slots must be reused, not grown"
    assert same_payload(disk.get(3), payload(3))


# ---------------------------------------------------------------------------
# scheduler integration: spill on evict, restore on miss, token identity
# ---------------------------------------------------------------------------

# NB: ``Scheduler.run`` returns every request the scheduler has EVER
# finished, oldest first — on a reused scheduler the request just
# submitted is ``[-1]``, not ``[0]``.

def spill_sched(engine, **over):
    kw = dict(spill_host_bytes=1 << 20)
    kw.update(over)
    return Scheduler(engine, **kw)


@pytest.mark.slow
def test_restore_from_spill_token_identity_plain(engine, big_engine):
    s = spill_sched(engine)
    warm = s.run([Request(SYS + [20, 21], 4)])[0]       # registers SYS page
    churn(s, (40, 45, 50, 55, 60))                              # evicts + spills it
    assert s.metrics.pages_spilled > 0, "churn must actually spill"
    hot = s.run([Request(SYS + [22, 23], 4)])[-1]       # restore path
    m = s.metrics.summary()
    assert m["pages_restored"] >= 1 and m["spill_host_hits"] >= 1
    assert m["restore_bytes"] > 0 and m["restore_s"] >= 0.0
    # oracle 1: recompute-prefill (fresh scheduler, spill off, same pool)
    rec = Scheduler(engine).run([Request(SYS + [22, 23], 4)])[0]
    # oracle 2: HBM hit (roomy pool, prefix never evicted)
    s2 = Scheduler(big_engine)
    s2.run([Request(SYS + [20, 21], 4)])
    hbm = s2.run([Request(SYS + [22, 23], 4)])[-1]
    assert hot.tokens == rec.tokens == hbm.tokens
    assert warm.error is None and hot.error is None
    # the restore counted as a prefix hit with its tokens accounted
    assert m["prefill_tokens_saved"] >= PAGE


def test_restore_token_identity_spec_and_chunked(engine):
    """The restore re-entry composes with BOTH fancy admission paths:
    speculative decode (suffix prefill + verify) and chunked prefill
    (the suffix arrives in verify-program windows), with the eviction
    happening mid-run between the warm and hot requests."""
    for extra in (dict(draft=NGramDraft(), ),
                  dict(chunk_tokens=8)):
        spec = 2 if "draft" in extra else 0
        s = spill_sched(engine, **extra)
        s.run([Request(SYS + [20, 21], 4, speculate=spec)])
        churn(s, (40, 45, 50, 55, 60))
        assert s.metrics.pages_spilled > 0
        hot = s.run([Request(SYS + [22, 23], 5, speculate=spec)])[-1]
        assert hot.error is None
        assert s.metrics.pages_restored >= 1, f"no restore under {extra}"
        # the pin the hierarchy owes: a restore-from-spill admission is
        # indistinguishable from an HBM prefix hit.  Oracle = the same
        # warm-then-hot sequence on a spill-free scheduler over the same
        # engine, so both sides take the prefix-hit admission path.
        o = Scheduler(engine, **extra)
        o.run([Request(SYS + [20, 21], 4, speculate=spec)])
        hbm = o.run([Request(SYS + [22, 23], 5, speculate=spec)])[-1]
        assert hot.tokens == hbm.tokens, f"diverged from HBM hit: {extra}"
        # and from a cold recompute of the same request
        ref = Scheduler(engine, **extra).run(
            [Request(SYS + [22, 23], 5, speculate=spec)])[0]
        assert hot.tokens == ref.tokens, \
            f"diverged from recompute under {extra}"


def test_disk_tier_restore_token_identity(engine, tmp_path):
    """A host budget too small for even one page forces every spill
    straight to the disk tier; the restore is a disk hit and still
    token-identical."""
    s = Scheduler(engine, spill_host_bytes=1,
                  spill_dir=str(tmp_path), spill_disk_bytes=1 << 20)
    s.run([Request(SYS + [20, 21], 4)])
    churn(s, (40, 45, 50, 55, 60))
    m = s.metrics.summary()
    assert m["pages_spilled"] > 0
    assert s.spill.disk.puts > 0, "tiny host budget must demote to disk"
    hot = s.run([Request(SYS + [22, 23], 4)])[-1]
    assert hot.error is None
    assert s.metrics.summary()["spill_disk_hits"] >= 1
    ref = Scheduler(engine).run([Request(SYS + [22, 23], 4)])[0]
    assert hot.tokens == ref.tokens


def test_corrupt_spill_falls_back_to_recompute(engine, tmp_path):
    """Mid-serving corruption of the spill file: the hot request's
    restore quarantines the record, recomputes, and still matches."""
    s = Scheduler(engine, spill_host_bytes=1,
                  spill_dir=str(tmp_path), spill_disk_bytes=1 << 20)
    s.run([Request(SYS + [20, 21], 4)])
    churn(s, (40, 45, 50, 55, 60))
    disk = s.spill.disk
    assert disk.puts > 0
    with open(disk.path, "r+b") as f:        # corrupt EVERY record
        f.seek(0)
        f.write(b"\xff" * (disk._n_slots * disk.record_bytes))
    import mmap
    disk._mm.close()
    disk._mm = mmap.mmap(disk._fh.fileno(),
                         disk._n_slots * disk.record_bytes)
    hot = s.run([Request(SYS + [22, 23], 4)])[-1]
    assert hot.error is None, "corruption must degrade, never fail"
    ref = Scheduler(engine).run([Request(SYS + [22, 23], 4)])[0]
    assert hot.tokens == ref.tokens
    assert disk.corrupt_entries > 0
    assert s.metrics.summary()["spill_quarantined"] > 0 \
        or s.metrics.summary()["pages_restored"] == 0


def test_spill_receipts_feed_kv_receipts(engine):
    s = spill_sched(engine)
    s.run([Request(SYS + [20, 21], 4)])
    ops = [op for op, _ in s.kv_receipts]
    assert "add" in ops, "registration must publish an add receipt"
    hashes = page_chain_hashes(SYS + [20, 21], PAGE)
    assert ("add", hashes[0]) in list(s.kv_receipts)


def test_spill_kwargs_validation(engine, model, params):
    with pytest.raises(ValueError, match="prefix_cache"):
        Scheduler(engine, spill_host_bytes=1 << 20, prefix_cache=False)
    dense = InferenceEngine(model, params, n_slots=2, buckets=BUCKETS)
    with pytest.raises(ValueError, match="paged"):
        Scheduler(dense, spill_host_bytes=1 << 20)


def test_spill_counters_are_window_counters():
    need = {"pages_spilled", "pages_restored", "spill_bytes",
            "restore_s", "directory_hits"}
    assert need <= ServeMetrics._WINDOW_COUNTERS
    # and they all exist in a fresh summary (exporter schema stability)
    m = ServeMetrics(n_slots=2).summary()
    for k in ("pages_spilled", "pages_restored", "spill_bytes",
              "restore_bytes", "spill_s", "restore_s", "spill_host_hits",
              "spill_disk_hits", "spill_quarantined", "directory_hits"):
        assert k in m, k
