"""Content-addressed checkpoint subsystem tests (elastic/state.py
``_CommitWriter`` + checkpoint/store.py ``BlobStore``).

Property coverage the ISSUE names: async == sync snapshot equivalence at
every commit cadence, dedup correctness (bit-identical restores when
blobs are shared across commits and ranks), digest-mismatch loudness,
GC/retention, torn-commit containment (a rank dying between blob write
and manifest publish must leave the previous complete manifest as the
restore point).
"""

import json
import os
import pickle
import subprocess
import sys
import textwrap
import time

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import elastic
from horovod_tpu.checkpoint.store import (BlobIntegrityError, BlobStore,
                                          blob_digest, newest_manifest_seq)
from horovod_tpu.elastic import state as state_mod


# --- BlobStore unit behavior ------------------------------------------------

def test_blob_put_get_roundtrip_and_dedup(tmp_path):
    store = BlobStore(str(tmp_path / "cas"))
    data = b"x" * 1000
    digest, wrote = store.put_blob(data)
    assert wrote and digest == blob_digest(data)
    assert store.get_blob(digest) == data
    # Idempotent: the second put of identical bytes writes nothing.
    digest2, wrote2 = store.put_blob(data)
    assert digest2 == digest and not wrote2
    assert store.stats["bytes_written"] == 1000
    assert store.stats["bytes_deduped"] == 1000
    assert store.stats["blobs_written"] == 1
    assert store.stats["blobs_deduped"] == 1


def test_blob_verify_at_read_raises_loudly(tmp_path):
    store = BlobStore(str(tmp_path / "cas"))
    digest, _ = store.put_blob(b"hello world" * 10)
    path = store.blob_path(digest)
    with open(path, "r+b") as fh:
        fh.seek(3)
        fh.write(b"\xff")
    with pytest.raises(BlobIntegrityError):
        store.get_blob(digest)
    # verify=False is the explicit escape hatch (peer-fetch re-hashing
    # happens at the receiving rank's put_blob).
    assert store.get_blob(digest, verify=False)


def test_manifest_publish_atomic_and_torn_skipped(tmp_path):
    store = BlobStore(str(tmp_path / "cas"))
    store.publish_manifest({"seq": 1, "skeleton": "ab", "leaves": []})
    store.publish_manifest({"seq": 2, "skeleton": "cd", "leaves": []})
    assert store.manifest_seqs() == [1, 2]
    # Tear manifest 2 (truncate mid-JSON): read returns None, newest
    # readable falls back to 1.
    with open(store.manifest_path(2), "r+b") as fh:
        fh.truncate(9)
    assert store.read_manifest(2) is None
    assert store.newest_manifest()["seq"] == 1
    assert store.newest_seq() == 1


def test_newest_manifest_seq_never_raises(tmp_path):
    assert newest_manifest_seq(str(tmp_path / "nope")) == -1
    assert newest_manifest_seq("") == -1


# --- async == sync equivalence at every cadence -----------------------------

def _drive(state, cadence, steps=7):
    """A deterministic fake training loop: mutate array + scalar attrs
    every step, commit every ``cadence`` steps."""
    for i in range(steps):
        state.step = i + 1
        state.params = {"w": state.params["w"] + 1.0,
                        "frozen": state.params["frozen"]}
        if (i + 1) % cadence == 0:
            state.save()
    assert state.flush_commits(timeout=30)


@pytest.mark.parametrize("cadence", [1, 2, 3, 5])
def test_async_equals_sync_snapshot_every_cadence(tmp_path, cadence):
    payload0 = lambda: {"w": jnp.arange(8.0), "frozen": jnp.ones(16)}  # noqa: E731
    d_async = str(tmp_path / f"async_{cadence}")
    d_sync = str(tmp_path / f"sync_{cadence}")
    sa = elastic.JaxState(commit_dir=d_async, commit_async=True,
                          params=payload0(), step=0)
    ss = elastic.JaxState(commit_dir=d_sync, commit_async=False,
                          params=payload0(), step=0)
    _drive(sa, cadence)
    _drive(ss, cadence)
    ra = elastic.JaxState(commit_dir=d_async, params=None, step=-1)
    rs = elastic.JaxState(commit_dir=d_sync, params=None, step=-1)
    assert ra.load_latest() and rs.load_latest()
    assert ra.step == rs.step and ra._commit_seq == rs._commit_seq
    for k in ("w", "frozen"):
        a, b = np.asarray(ra.params[k]), np.asarray(rs.params[k])
        assert a.tobytes() == b.tobytes()   # bit-identical
    # In-memory rollback snapshots match the persisted commit too.
    assert np.asarray(sa._saved["params"]["w"]).tobytes() \
        == np.asarray(ra.params["w"]).tobytes()


# --- dedup ------------------------------------------------------------------

def test_frozen_leaves_dedup_across_commits(tmp_path):
    d = str(tmp_path / "commits")
    frozen = jnp.arange(4096.0)       # 16 KiB leaf, never touched
    s = elastic.JaxState(commit_dir=d, params={"w": jnp.zeros(8),
                                               "frozen": frozen}, step=0)
    for i in range(4):
        s.step = i
        s.params = {"w": s.params["w"] + 1.0, "frozen": s.params["frozen"]}
        s.save()
    assert s.flush_commits(timeout=30)
    stats = s._writer.store.stats
    # The frozen leaf's bytes were written exactly once (identity cache
    # short-circuits even the fetch after commit 1); later commits write
    # only the small changed leaves + manifest-pinned skeleton.
    frozen_bytes = len(pickle.dumps(np.asarray(frozen), protocol=4))
    assert stats["bytes_written"] < 4 * frozen_bytes
    assert stats["bytes_written"] > 0


def test_identical_content_dedups_across_ranks(tmp_path):
    """Two states sharing a commit dir (two ranks on a shared disk):
    the second rank's identical leaves land on existing addresses and
    cost zero written bytes."""
    d = str(tmp_path / "commits")
    mk = lambda: {"w": jnp.arange(1024.0)}  # noqa: E731
    a = elastic.JaxState(commit_dir=d, commit_async=False, params=mk(),
                         step=0)
    a.save()
    b = elastic.JaxState(commit_dir=d, commit_async=False, params=mk(),
                         step=0)
    b.save()
    stats = b._writer.store.stats
    assert stats["blobs_deduped"] >= 1          # the big leaf, at least
    assert stats["bytes_deduped"] > stats["bytes_written"]
    # And the shared-store restore is bit-identical.
    r = elastic.JaxState(commit_dir=d, params=None, step=-1)
    assert r.load_latest()
    assert np.asarray(r.params["w"]).tobytes() \
        == np.asarray(mk()["w"]).tobytes()


# --- GC / retention ---------------------------------------------------------

def test_gc_retention_keeps_newest_k_and_sweeps_blobs(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_CHECKPOINT_KEEP", "2")
    d = str(tmp_path / "commits")
    s = elastic.JaxState(commit_dir=d, commit_async=False,
                         params={"w": jnp.zeros(2048)}, step=0)
    digests_per_commit = []
    for i in range(5):
        s.step = i
        s.params = {"w": s.params["w"] + 1.0}
        s.save()
        store = s._writer.store
        m = store.read_manifest(s._commit_seq)
        digests_per_commit.append([e[0] for e in m["leaves"]])
        time.sleep(0.02)   # distinct mtimes for the GC age guard
    store = state_mod._cas_store(d)
    assert store.manifest_seqs() == [4, 5]
    # Blobs only the dropped manifests referenced are gone; kept ones stay.
    kept_refs = store.referenced_digests(
        [store.read_manifest(4), store.read_manifest(5)])
    for digest in digests_per_commit[0]:
        if digest not in kept_refs:
            assert not store.has_blob(digest)
    for digest in digests_per_commit[-1]:
        assert store.has_blob(digest)
    # Restores still work after the sweep.
    r = elastic.JaxState(commit_dir=d, params=None, step=-1)
    assert r.load_latest() and r._commit_seq == 5


def test_gc_never_drops_last_manifest(tmp_path):
    store = BlobStore(str(tmp_path / "cas"))
    digest, _ = store.put_blob(b"payload")
    store.publish_manifest({"seq": 1, "skeleton": digest, "leaves": []})
    stats = store.gc(0)     # keep clamps to 1
    assert stats["manifests_removed"] == 0
    assert store.manifest_seqs() == [1]
    assert store.has_blob(digest)


def test_gc_keeps_pinned_manifest_and_blobs_past_retention(tmp_path):
    """Publish pins (serving plane) hold a manifest + its blobs no matter
    how far HOROVOD_CHECKPOINT_KEEP has moved past it; unpinning releases
    it to the next sweep."""
    store = BlobStore(str(tmp_path / "cas"))
    digests = []
    for seq in range(1, 6):
        digest, _ = store.put_blob(b"gen-%d" % seq)
        digests.append(digest)
        store.publish_manifest({"seq": seq, "skeleton": digest,
                                "leaves": [[digest, 6]]})
        time.sleep(0.02)    # distinct mtimes for the GC age guard
    pin_path = store.pin_manifest(2, meta={"published": True,
                                           "leaves_digest": "ab"})
    assert os.path.exists(pin_path)
    assert store.pinned_seqs() == [2]
    assert store.read_pin(2)["leaves_digest"] == "ab"
    stats = store.gc(1)
    # pinned seq 2 + newest seq 5 survive; 1, 3, 4 are swept
    assert store.manifest_seqs() == [2, 5]
    assert stats["manifests_removed"] == 3
    assert store.has_blob(digests[1]) and store.has_blob(digests[4])
    # gen-1's blob predates every kept manifest: swept. gen-3/4 blobs are
    # NEWER than pinned manifest 2, so the concurrent-writer age guard
    # retains them (they go once the retention window moves on).
    assert not store.has_blob(digests[0])
    # pinned content is still verifiably readable (a serving process may
    # be mid-delta-fetch against it)
    assert store.get_blob(digests[1], verify=True) == b"gen-2"
    # unpin -> swept by the next pass; double-unpin reports False
    assert store.unpin_manifest(2) is True
    assert store.unpin_manifest(2) is False
    store.gc(1)
    assert store.manifest_seqs() == [5]
    assert not store.has_blob(digests[1])


# --- per-shard blob layer (ISSUE 14) ----------------------------------------

def _clean_counters():
    return {"steps_skipped": 0.0, "rollbacks": 0.0}


def _decode_like_params(seed, dim=32, hidden=64):
    """A params tree keyed like the decode plane's (attn/mlp kernels),
    small enough to publish in milliseconds — ``tp_shard_plan`` matches
    on path names and shapes, not on flax types."""
    rng = np.random.RandomState(seed)
    leaf = lambda *s: rng.randn(*s).astype(np.float32)  # noqa: E731
    return {
        "tok_embeddings": {"embedding": leaf(64, dim)},   # replicated
        "block_0": {
            "attn": {"wq": {"kernel": leaf(dim, dim)},    # column (axis 1)
                     "wo": {"kernel": leaf(dim, dim)}},   # row (axis 0)
            "mlp": {"w1": {"kernel": leaf(dim, hidden)},
                    "w2": {"kernel": leaf(hidden, dim)}},
        },
    }


def _publish_params(tmp_path, name, params, shard_plan=None):
    from horovod_tpu.elastic.state import ObjectState
    from horovod_tpu.serving.publisher import Publisher
    d = str(tmp_path / name)
    state = ObjectState(commit_dir=d, commit_async=False, params=params)
    pub = Publisher(d, every=1, counters=_clean_counters,
                    shard_plan=shard_plan)
    state.commit()
    rec = pub.maybe_publish(state._commit_seq)
    assert rec is not None
    return state, pub, rec


def test_shard_manifest_roundtrip_and_identity(tmp_path):
    """Manifest encode/decode: every planned leaf gets an
    ``shards[leaf_digest] = {axis, n, parts}`` entry whose parts
    re-concatenate bit-identically to the whole-leaf blob — and the
    shard layer does NOT change ``leaves_digest`` (the served identity
    covers skeleton + leaf digests only)."""
    from horovod_tpu.serving.decode import tp_shard_plan
    from horovod_tpu.serving.publisher import leaves_digest

    tp = 4
    params = _decode_like_params(0)
    state, pub, rec = _publish_params(tmp_path, "cas", params,
                                      shard_plan=tp_shard_plan(tp))
    manifest = pub.store.read_manifest(rec["manifest_seq"])
    shards = manifest["shards"]
    # wq/wo/w1/w2 kernels planned; the embedding is replicated (no entry).
    assert len(shards) == 4
    leaf_bytes = {e[0]: e[1] for e in manifest["leaves"]}
    for digest, meta in shards.items():
        assert meta["n"] == tp and len(meta["parts"]) == tp
        assert meta["axis"] in (0, 1)
        whole = pickle.loads(pub.store.get_blob(digest, verify=True))
        parts = [pickle.loads(pub.store.get_blob(p[0], verify=True))
                 for p in meta["parts"]]
        np.testing.assert_array_equal(
            np.concatenate(parts, axis=meta["axis"]), whole)
        for p in meta["parts"]:
            assert p[1] > 0
        assert digest in leaf_bytes                 # whole leaf stays
    # Identity: stripping the shard layer leaves the digest unchanged.
    bare = {k: v for k, v in manifest.items() if k != "shards"}
    assert leaves_digest(manifest) == leaves_digest(bare) \
        == rec["leaves_digest"]


def test_shard_read_compat_both_ways(tmp_path):
    """Old reader × new manifest and new reader × old manifest both
    restore bit-identical payloads: whole-leaf blobs stay authoritative."""
    from horovod_tpu.serving.decode import tp_shard_plan, tp_shard_selector
    from horovod_tpu.serving.registry import ModelRegistry

    params = _decode_like_params(3)
    # New manifest (with shards), plain registry (no selector).
    _, pub, rec = _publish_params(tmp_path, "new", params,
                                  shard_plan=tp_shard_plan(4))
    plain = ModelRegistry(store=pub.store)
    assert plain.adopt(rec)
    got = plain.current().payload["attrs"]["params"]
    for k in ("wq", "wo"):
        np.testing.assert_array_equal(
            np.asarray(got["block_0"]["attn"][k]["kernel"]),
            params["block_0"]["attn"][k]["kernel"])
    # Old manifest (no shards), shard-selecting registry: falls back to
    # the whole leaf and still lands the complete payload.
    _, pub2, rec2 = _publish_params(tmp_path, "old", params)
    assert "shards" not in pub2.store.read_manifest(rec2["manifest_seq"])
    sel = ModelRegistry(store=pub2.store,
                        shard_selector=tp_shard_selector(4, 1))
    assert sel.adopt(rec2)
    got2 = sel.current().payload["attrs"]["params"]
    np.testing.assert_array_equal(
        np.asarray(got2["block_0"]["mlp"]["w1"]["kernel"]),
        params["block_0"]["mlp"]["w1"]["kernel"])


def test_shard_delta_fetch_counts_and_topology_change(tmp_path):
    """A shard-selecting registry fetches only its part bytes for planned
    leaves; a selector whose tp does NOT match the manifest's shard count
    (topology changed between publish and serve) falls back to whole
    leaves — correct first, cheap second."""
    from horovod_tpu.serving.decode import tp_shard_plan, tp_shard_selector
    from horovod_tpu.serving.registry import ModelRegistry

    tp = 4
    state, pub, rec = _publish_params(tmp_path, "cas",
                                      _decode_like_params(1),
                                      shard_plan=tp_shard_plan(tp))
    full = ModelRegistry(store=pub.store)
    shard = ModelRegistry(store=pub.store,
                          shard_selector=tp_shard_selector(tp, 2))
    mismatch = ModelRegistry(store=pub.store,
                             shard_selector=tp_shard_selector(2, 1))
    assert full.adopt(rec) and shard.adopt(rec) and mismatch.adopt(rec)
    fb = full.stats["bytes_fetched"]
    sb = shard.stats["bytes_fetched"]
    mb = mismatch.stats["bytes_fetched"]
    # Sharded leaves dominate this tree, so the delta is well under 1/2.
    assert 0 < sb < fb / 2, (sb, fb)
    # n=4 manifest × tp=2 selector: every leaf falls back to whole bytes.
    assert mb == fb, (mb, fb)
    # The mismatch payload is still complete and correct.
    got = mismatch.current().payload["attrs"]["params"]
    np.testing.assert_array_equal(
        np.asarray(got["block_0"]["attn"]["wo"]["kernel"]),
        np.asarray(full.current()
                   .payload["attrs"]["params"]["block_0"]["attn"]["wo"]
                   ["kernel"]))
    # And the shard registry's planned leaves are the right slices.
    wq = np.asarray(shard.current()
                    .payload["attrs"]["params"]["block_0"]["attn"]["wq"]
                    ["kernel"])
    wq_full = np.asarray(full.current()
                         .payload["attrs"]["params"]["block_0"]["attn"]
                         ["wq"]["kernel"])
    np.testing.assert_array_equal(wq, np.split(wq_full, tp, axis=1)[2])


def test_corrupted_shard_part_rejected_keeps_generation(tmp_path):
    """One bit-flipped part blob must fail adoption LOUDLY on the shard
    registry — which keeps serving its previous generation — while the
    whole-leaf path (intact blobs) adopts the same publish fine."""
    from horovod_tpu.serving.decode import tp_shard_plan, tp_shard_selector
    from horovod_tpu.serving.registry import ModelRegistry

    tp = 4
    state, pub, rec = _publish_params(tmp_path, "cas",
                                      _decode_like_params(5),
                                      shard_plan=tp_shard_plan(tp))
    full = ModelRegistry(store=pub.store)
    shard = ModelRegistry(store=pub.store,
                          shard_selector=tp_shard_selector(tp, 0))
    assert full.adopt(rec) and shard.adopt(rec)

    state.params = _decode_like_params(6)
    state.commit()
    rec2 = pub.maybe_publish(state._commit_seq)
    manifest = pub.store.read_manifest(rec2["manifest_seq"])
    part_digest = next(iter(manifest["shards"].values()))["parts"][0][0]
    with open(pub.store.blob_path(part_digest), "r+b") as fh:
        fh.seek(10)
        fh.write(b"\xff\xff\xff")

    kept = shard.current().manifest_seq
    rejected = shard.stats["rejected"]
    assert shard.adopt(rec2) is False
    assert shard.current().manifest_seq == kept      # generation kept
    assert shard.stats["rejected"] == rejected + 1
    assert full.adopt(rec2)                          # whole leaves intact


def test_gc_keeps_shard_part_blobs_of_live_manifests(tmp_path):
    """``referenced_digests`` names part blobs, so GC cannot sweep the
    shard layer out from under a live (or pinned) manifest; dropping the
    manifest releases the parts like any other blob."""
    store = BlobStore(str(tmp_path / "cas"))
    leaf, _ = store.put_blob(b"leaf-bytes" * 100)
    p1, _ = store.put_blob(b"part-one")
    p2, _ = store.put_blob(b"part-two")
    store.publish_manifest({
        "seq": 1, "skeleton": leaf, "leaves": [[leaf, 1000]],
        "shards": {leaf: {"axis": 0, "n": 2,
                          "parts": [[p1, 8], [p2, 8]]}}})
    refs = store.referenced_digests([store.read_manifest(1)])
    assert p1 in refs and p2 in refs
    time.sleep(0.02)
    d2, _ = store.put_blob(b"gen-2")
    store.publish_manifest({"seq": 2, "skeleton": d2, "leaves": [[d2, 5]]})
    store.gc(2)                     # both manifests live: parts survive
    assert store.has_blob(p1) and store.has_blob(p2)
    time.sleep(0.02)
    store.gc(1)                     # manifest 1 swept: parts released
    assert store.manifest_seqs() == [2]
    assert not store.has_blob(p1) and not store.has_blob(p2)


# --- peer-sourced resume (ISSUE 18) -----------------------------------------

_MESH_KEY = b"k" * 32


def _peer_restore(src_store, manifest, dst_dir, shard_selector=None):
    """Simulate a fresh rank restoring over the blob mesh: an EMPTY local
    store, the need set computed from the manifest under the selector,
    every blob fetched point-to-point from a real loopback
    ``BlobPeerService`` — exactly the multi-process resume path of
    ``load_persisted_world`` minus the collectives. Returns
    ``(payload, bytes_fetched)``."""
    from horovod_tpu.elastic import blobmesh
    dst = BlobStore(str(dst_dir))
    svc = blobmesh.BlobPeerService(src_store, _MESH_KEY,
                                   bind_host="127.0.0.1", rank=0)
    addr = {0: f"127.0.0.1:{svc.port}"}
    fetched = 0
    try:
        skel = [manifest["skeleton"]]
        s = blobmesh.fetch_missing(dst, skel, {skel[0]: [0]}, addr,
                                   _MESH_KEY)
        fetched += s["bytes_fetched"]
        need = state_mod._manifest_need(dst, manifest, shard_selector)
        missing = [d for d in need if not dst.has_blob(d)]
        s = blobmesh.fetch_missing(
            dst, missing,
            blobmesh.assign_sources(missing, {0: set(missing)}, 0),
            addr, _MESH_KEY)
        fetched += s["bytes_fetched"]
    finally:
        svc.close()
    return state_mod._unpack_manifest(dst, manifest, shard_selector), fetched


def _leaves_bytes(tree):
    import jax
    return [np.asarray(l).tobytes() for l in jax.tree_util.tree_leaves(tree)]


def test_peer_resume_equals_local_restore_bit_identical(tmp_path):
    """Property (same topology AND the regrown-world case — a brand-new
    rank owns NO blobs): a payload materialized entirely over the peer
    mesh is bit-identical to the committing rank's local restore, and the
    fetched bytes account for exactly skeleton + every whole leaf."""
    d = str(tmp_path / "commits")
    s = elastic.JaxState(commit_dir=d, commit_async=False,
                         params={"w": jnp.arange(256.0),
                                 "b": jnp.ones(32)}, step=0)
    s.save()
    store = state_mod._cas_store(d)
    manifest = store.read_manifest(s._commit_seq)
    local = state_mod._unpack_manifest(store, manifest)
    peer, fetched = _peer_restore(store, manifest, tmp_path / "fresh")
    assert _leaves_bytes(peer) == _leaves_bytes(local)
    expected = len(store.get_blob(manifest["skeleton"])) \
        + sum(e[1] for e in manifest["leaves"])
    assert fetched == expected


def test_peer_resume_resharded_world_delta_and_identity(tmp_path):
    """Topology-change restore (serving-style tp reshape): each target
    shard fetches ONLY its part blobs for planned leaves — byte
    accounting proves the delta — and the selected slices re-concatenate
    bit-identically to the whole-leaf restore."""
    from horovod_tpu.serving.decode import tp_shard_plan, tp_shard_selector
    tp = 4
    params = _decode_like_params(11)
    _state, pub, rec = _publish_params(tmp_path, "cas", params,
                                       shard_plan=tp_shard_plan(tp))
    manifest = pub.store.read_manifest(rec["manifest_seq"])
    full, full_bytes = _peer_restore(pub.store, manifest,
                                     tmp_path / "full")
    got_wq = []
    for idx in range(tp):
        part, part_bytes = _peer_restore(
            pub.store, manifest, tmp_path / f"shard{idx}",
            shard_selector=tp_shard_selector(tp, idx))
        assert 0 < part_bytes < full_bytes / 2, (part_bytes, full_bytes)
        # unplanned leaves ride whole (bit-identical to the full restore)
        emb = part["attrs"]["params"]["tok_embeddings"]["embedding"]
        assert np.asarray(emb).tobytes() == np.asarray(
            full["attrs"]["params"]["tok_embeddings"]["embedding"]).tobytes()
        got_wq.append(np.asarray(
            part["attrs"]["params"]["block_0"]["attn"]["wq"]["kernel"]))
    wq_full = np.asarray(
        full["attrs"]["params"]["block_0"]["attn"]["wq"]["kernel"])
    np.testing.assert_array_equal(np.concatenate(got_wq, axis=1), wq_full)
    assert np.concatenate(got_wq, axis=1).tobytes() == wq_full.tobytes()


def test_peer_resume_topology_mismatch_whole_leaf_fallback(tmp_path):
    """A selector whose tp does not divide the manifest's shard count
    falls back to whole leaves: the need set names no part blobs, the
    fetched bytes equal the full restore, and the payload is complete."""
    from horovod_tpu.serving.decode import tp_shard_plan, tp_shard_selector
    params = _decode_like_params(12)
    _state, pub, rec = _publish_params(tmp_path, "cas", params,
                                       shard_plan=tp_shard_plan(4))
    manifest = pub.store.read_manifest(rec["manifest_seq"])
    part_digests = {p[0] for m in manifest["shards"].values()
                    for p in m["parts"]}
    need = state_mod._manifest_need(pub.store, manifest,
                                    tp_shard_selector(2, 1))
    assert not (set(need) & part_digests)
    full, full_bytes = _peer_restore(pub.store, manifest, tmp_path / "f")
    mism, mism_bytes = _peer_restore(pub.store, manifest, tmp_path / "m",
                                     shard_selector=tp_shard_selector(2, 1))
    assert mism_bytes == full_bytes
    assert _leaves_bytes(mism) == _leaves_bytes(full)


def test_load_persisted_world_single_process_selector(tmp_path):
    """``load_persisted_world`` (single-process path) honors the shard
    selector: planned leaves come back as the target shard's slice,
    bit-identical to slicing the whole-leaf restore."""
    from horovod_tpu.serving.decode import tp_shard_plan, tp_shard_selector
    tp, idx = 4, 2
    params = _decode_like_params(13)
    _state, pub, rec = _publish_params(tmp_path, "cas", params,
                                       shard_plan=tp_shard_plan(tp))
    d = str(tmp_path / "cas")
    whole = state_mod.load_persisted_world(d)
    sliced = state_mod.load_persisted_world(
        d, shard_selector=tp_shard_selector(tp, idx))
    wq_whole = np.asarray(
        whole["attrs"]["params"]["block_0"]["attn"]["wq"]["kernel"])
    wq_slice = np.asarray(
        sliced["attrs"]["params"]["block_0"]["attn"]["wq"]["kernel"])
    np.testing.assert_array_equal(
        wq_slice, np.split(wq_whole, tp, axis=1)[idx])
    assert wq_slice.tobytes() \
        == np.split(wq_whole, tp, axis=1)[idx].tobytes()


def test_load_persisted_world_legacy_single_frame_fallback(tmp_path):
    """A commit dir holding only a legacy single-frame commit (no CAS
    manifest) still restores through ``load_persisted_world`` — with or
    without a selector (the selector needs a manifest to act on)."""
    d = str(tmp_path / "legacy")
    os.makedirs(d)
    payload = {"seq": 3, "attrs": {"w": np.arange(16.0)}}
    state_mod._persist(d, payload)
    got = state_mod.load_persisted_world(d)
    assert got["seq"] == 3
    np.testing.assert_array_equal(got["attrs"]["w"], np.arange(16.0))
    got2 = state_mod.load_persisted_world(
        d, shard_selector=lambda names, meta: None)
    assert np.asarray(got2["attrs"]["w"]).tobytes() \
        == np.asarray(got["attrs"]["w"]).tobytes()


# --- torn commit (crash between blob write and manifest publish) ------------

_TORN_WORKER = textwrap.dedent("""
    import os, sys
    import jax
    import jax.numpy as jnp
    import horovod_tpu
    from horovod_tpu import elastic
    from horovod_tpu.testing import faults

    commit_dir = sys.argv[1]
    s = elastic.JaxState(commit_dir=commit_dir,
                         params={"w": jnp.arange(8.0)}, step=0)
    faults.on_step(0, rank=0)
    s.step = 1
    s.params = {"w": s.params["w"] + 1.0}
    s.save()
    assert s.flush_commits(timeout=30)      # commit 1 fully published
    faults.on_step(1, rank=0)               # arms the torn fault
    s.step = 2
    s.params = {"w": s.params["w"] + 1.0}
    s.save()
    # Commit 2's writer dies between blob write and manifest publish —
    # this flush never returns.
    s.flush_commits(timeout=30)
    print("UNREACHABLE", flush=True)
    sys.exit(3)
""")


@pytest.mark.slow
def test_torn_commit_restores_previous_manifest(tmp_path):
    """Kill the committing process between blob write and manifest
    publish (``torn`` fault): the store holds commit 2's orphan blobs
    but only commit 1's manifest, and restore lands on commit 1 — never
    a mixed state."""
    script = tmp_path / "torn_worker.py"
    script.write_text(_TORN_WORKER)
    d = str(tmp_path / "commits")
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH", ""),
        "HOROVOD_FAULT_SPEC": "torn:rank=0,step=1",
        "HOROVOD_FAULT_MARKER_DIR": str(tmp_path / "markers"),
        "HOROVOD_RANK": "0",
    })
    r = subprocess.run([sys.executable, str(script), d], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "UNREACHABLE" not in r.stdout
    assert "torn commit" in (r.stdout + r.stderr)
    store = state_mod._cas_store(d)
    assert store.manifest_seqs() == [1]         # commit 2 never published
    s2 = elastic.JaxState(commit_dir=d, params=None, step=-1)
    assert s2.load_latest()
    assert s2.step == 1 and s2._commit_seq == 1
    np.testing.assert_array_equal(np.asarray(s2.params["w"]),
                                  np.arange(8.0) + 1.0)


# --- telemetry / incident wiring --------------------------------------------

def test_commit_telemetry_counters_and_stall_metric(tmp_path):
    from horovod_tpu.core import telemetry as _telemetry
    sess = _telemetry.active()
    if not sess.enabled:
        pytest.skip("telemetry disabled in this session")
    d = str(tmp_path / "commits")
    s = elastic.JaxState(commit_dir=d, params={"w": jnp.zeros(512)}, step=0)
    s.params = {"w": s.params["w"] + 1.0}
    s.save()
    assert s.flush_commits(timeout=30)
    snap = sess.registry.export()
    keys = set(snap["c"]) | set(snap["g"])
    assert any(k.startswith("hvd_checkpoint_bytes_written_total")
               for k in keys)
    assert any(k.startswith("hvd_commit_stall_seconds") for k in keys)
    assert any(k.startswith("hvd_last_manifest_seq") for k in keys)


def test_incident_report_names_last_manifest(tmp_path):
    from horovod_tpu.core import telemetry as _telemetry
    path = _telemetry.assemble_incident(
        str(tmp_path), 1, failure={"generation": 0, "last_manifest": 7})
    assert path is not None
    with open(path) as fh:
        report = json.load(fh)
    assert report["last_manifest"] == 7


def test_incident_last_manifest_falls_back_to_rank_events(tmp_path):
    from horovod_tpu.core import telemetry as _telemetry
    with open(os.path.join(str(tmp_path), "flight_0.jsonl"), "w") as fh:
        fh.write(json.dumps({"t": 0.0, "kind": "manifest_publish",
                             "seq": 3}) + "\n")
        fh.write(json.dumps({"t": 1.0, "kind": "manifest_publish",
                             "seq": 5}) + "\n")
    path = _telemetry.assemble_incident(str(tmp_path), 2,
                                        failure={"generation": 1})
    with open(path) as fh:
        report = json.load(fh)
    assert report["last_manifest"] == 5
