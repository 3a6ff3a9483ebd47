"""End-to-end serving plane: a REAL np=3 elastic world trains and
publishes generations through the CAS while a separate serving process
(this test) hot-swaps and answers HTTP requests throughout.

Acceptance (ISSUE 10): ≥2 generations published and hot-swapped with
ZERO dropped/failed requests, and after every swap the served weights'
``leaves_digest`` equals the published pin's — the serving pointer is
provably the announced generation, not a torn mix. The slow chaos
variant grows the world np=2→3 mid-publish and injects one blob
corruption between publish and adoption: the corrupt generation is
rejected (``hvd_serving_rejected_total``), the server keeps answering on
the previous weights, and a later clean publish is adopted.

Store-watch discovery is used deliberately: the launcher generates its
own HMAC secret per job, so an external serving process authenticates
by reading publish pins from the shared commit dir (docs/serving.md);
the coordinator announce path is covered in-process by
tests/test_serving.py.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from horovod_tpu.checkpoint.store import BlobStore
from horovod_tpu.serving import InferenceServer, ModelRegistry
from horovod_tpu.serving.publisher import leaves_digest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

E2E_WORKER = """
import json
import os
import time
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic, serving
from horovod_tpu.elastic import constants as C

hvd.init()
commit_dir = os.environ[C.COMMIT_DIR_ENV]
pub = None
if hvd.rank() == 0:
    pub = serving.attach(commit_dir, every=1)
    tmp = os.environ["COMMIT_DIR_OUT"] + ".tmp"
    with open(tmp, "w") as f:
        f.write(commit_dir)
    os.replace(tmp, os.environ["COMMIT_DIR_OUT"])

state = elastic.ObjectState(step=0, w=np.zeros(16, np.float32))

@elastic.run
def train(state):
    while state.step < int(os.environ.get("E2E_STEPS", "6")):
        state.step += 1
        state.w = state.w + 1.0
        gm = os.environ.get("GROW_MARKER")
        if (gm and hvd.rank() == 0 and state.step == 2
                and not os.path.exists(gm)):
            with open(gm, "w") as f:
                f.write("grown")
            with open(os.environ["GROW_HOSTS_FILE"], "w") as f:
                f.write("localhost:1\\n127.0.0.2:1\\n127.0.0.3:1\\n")
        time.sleep(0.25)
        state.commit()
    return state.step

train(state)
state.flush_commits(timeout=60)
# Hold the generation (and with it the shared commit dir, which the
# driver deletes on exit) until the serving side finished verifying.
deadline = time.time() + 120
while (not os.path.exists(os.environ["DONE_MARKER"])
       and time.time() < deadline):
    time.sleep(0.1)
print(json.dumps({"trained": True, "size": hvd.size(),
                  "rank": hvd.rank()}), flush=True)
"""


def _spawn_world(tmp_path, hosts_lines, extra_args, env_extra):
    disco = tmp_path / "discover.sh"
    hosts_file = tmp_path / "hosts"
    hosts_file.write_text(hosts_lines)
    disco.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    disco.chmod(0o755)
    script = tmp_path / "e2e_worker.py"
    script.write_text(E2E_WORKER)
    env = dict(os.environ, **env_extra)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("HOROVOD_FAULT_SPEC", None)
    env["GROW_HOSTS_FILE"] = str(hosts_file)
    return subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         *extra_args, "--host-discovery-script", str(disco),
         sys.executable, str(script)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)


def _wait_commit_dir(out_file, proc, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(out_file):
            with open(out_file) as f:
                return f.read().strip()
        if proc.poll() is not None:
            out, err = proc.communicate(timeout=30)
            raise AssertionError(
                f"launcher died before first publish: {out[-2000:]}\n"
                f"{err[-2000:]}")
        time.sleep(0.05)
    raise AssertionError("no commit dir announced within budget")


def _predict(addr, x):
    body = json.dumps({"x": float(x)}).encode()
    req = urllib.request.Request(
        f"http://{addr}/predict", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _assert_served_digest_matches_pin(reg, store):
    cur = reg.current()
    pin = store.read_pin(cur.manifest_seq)
    assert pin is not None and pin.get("published"), pin
    assert pin["leaves_digest"] == cur.leaves_digest
    # and against the manifest itself, not just the announcement
    assert leaves_digest(
        store.read_manifest(cur.manifest_seq)) == cur.leaves_digest


def _finish(proc, done_marker, timeout=120):
    with open(done_marker, "w") as f:
        f.write("done")
    out, err = proc.communicate(timeout=timeout)
    return proc.returncode, out, err


@pytest.mark.integration
def test_e2e_elastic_world_serves_across_hot_swaps(tmp_path):
    """np=3 world publishes ≥2 generations while this process serves
    HTTP requests through every hot-swap: zero dropped, digest-equal."""
    out_file = str(tmp_path / "commit_dir.txt")
    done = str(tmp_path / "done")
    proc = _spawn_world(
        tmp_path, "localhost:1\n127.0.0.2:1\n127.0.0.3:1\n",
        ["-np", "3", "--min-np", "3", "--max-np", "3"],
        {"COMMIT_DIR_OUT": out_file, "DONE_MARKER": done,
         "E2E_STEPS": "6"})
    srv = None
    try:
        commit_dir = _wait_commit_dir(out_file, proc)
        store = BlobStore(os.path.join(commit_dir, "cas"))
        reg = ModelRegistry(store=store)

        def forward(payload, inputs, padded_n):
            w = payload["attrs"]["w"]
            return [float(w[0]) + float(q["x"]) for q in inputs]

        srv = InferenceServer(reg, forward, window_s=0.002,
                              request_timeout_s=30.0)
        sent = ok = 0
        swap_seqs = []
        seq_to_w = {}
        deadline = time.time() + 180
        while time.time() < deadline:
            if reg.poll_store(store):
                cur = reg.current()
                swap_seqs.append(cur.manifest_seq)
                seq_to_w[cur.manifest_seq] = float(
                    cur.payload["attrs"]["w"][0])
                _assert_served_digest_matches_pin(reg, store)
            if reg.current() is not None:
                out = _predict(srv.addr(), sent)
                sent += 1
                ok += bool(out.get("ok"))
                # served answer reflects the served generation's weights
                assert out["result"] == pytest.approx(
                    seq_to_w[out["model_seq"]] + (sent - 1))
            if len(swap_seqs) >= 2 and sent >= 20 \
                    and reg.current().manifest_seq >= 6:
                break
            time.sleep(0.02)
        rc, pout, perr = _finish(proc, done)
        assert rc == 0, f"{pout[-3000:]}\n{perr[-3000:]}"
        assert len(swap_seqs) >= 2, swap_seqs     # >=2 hot-swaps happened
        assert sent >= 20 and ok == sent          # zero dropped/failed
        assert reg.stats["rejected"] == 0
        # all three final-generation workers reached the end
        lines = [json.loads(l) for l in pout.splitlines()
                 if l.startswith("{")]
        assert len(lines) == 3 and all(l["size"] == 3 for l in lines)
    finally:
        if srv is not None:
            srv.close()
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)


@pytest.mark.slow
@pytest.mark.integration
def test_e2e_chaos_corrupt_publish_during_elastic_grow(tmp_path):
    """Publishes keep flowing through an np=2→3 grow; one injected blob
    corruption between publish and adoption is rejected (fallback to the
    previous weights, requests keep succeeding), and a later clean
    publish is adopted with digest equality."""
    out_file = str(tmp_path / "commit_dir.txt")
    done = str(tmp_path / "done")
    proc = _spawn_world(
        tmp_path, "localhost:1\n127.0.0.2:1\n",
        ["-np", "2", "--min-np", "2", "--max-np", "3"],
        {"COMMIT_DIR_OUT": out_file, "DONE_MARKER": done,
         "E2E_STEPS": "8", "GROW_MARKER": str(tmp_path / "grown")})
    srv = None
    try:
        commit_dir = _wait_commit_dir(out_file, proc)
        store = BlobStore(os.path.join(commit_dir, "cas"))
        reg = ModelRegistry(store=store)

        def forward(payload, inputs, padded_n):
            return [float(payload["attrs"]["w"][0]) for _ in inputs]

        srv = InferenceServer(reg, forward, window_s=0.002,
                              request_timeout_s=30.0)
        sent = ok = 0
        corrupted_seq = None
        swaps = 0
        deadline = time.time() + 240
        while time.time() < deadline:
            pins = [s for s in store.pinned_seqs()
                    if (store.read_pin(s) or {}).get("published")]
            newest = max(pins) if pins else None
            cur = reg.current()
            if (newest is not None and corrupted_seq is None
                    and cur is not None and newest > cur.manifest_seq):
                # Inject: flip bytes in a CHANGED blob of the about-to-be
                # adopted generation, adopt (must reject), then restore.
                rec = store.read_pin(newest)
                manifest = store.read_manifest(newest)
                prev = store.read_manifest(cur.manifest_seq)
                if manifest is not None and prev is not None:
                    changed = ({e[0] for e in manifest["leaves"]}
                               - {e[0] for e in prev["leaves"]})
                    if changed:
                        victim = store.blob_path(sorted(changed)[0])
                        with open(victim, "rb") as f:
                            orig = f.read()
                        with open(victim, "wb") as f:
                            f.write(b"\x00" * len(orig))
                        assert reg.adopt(rec) is False
                        assert reg.current().manifest_seq \
                            == cur.manifest_seq       # fallback held
                        with open(victim, "wb") as f:
                            f.write(orig)
                        corrupted_seq = newest
            if reg.poll_store(store):
                swaps += 1
                _assert_served_digest_matches_pin(reg, store)
            if reg.current() is not None:
                out = _predict(srv.addr(), sent)
                sent += 1
                ok += bool(out.get("ok"))
            if (corrupted_seq is not None and swaps >= 2 and sent >= 20
                    and reg.current().manifest_seq >= corrupted_seq):
                break
            time.sleep(0.02)
        rc, pout, perr = _finish(proc, done)
        assert rc == 0, f"{pout[-3000:]}\n{perr[-3000:]}"
        assert corrupted_seq is not None, "chaos injection never fired"
        assert reg.stats["rejected"] >= 1         # the corrupt generation
        assert swaps >= 2
        assert sent >= 20 and ok == sent          # zero dropped/failed
        # the rejected generation (or a newer one) was later adopted
        # clean (digest equality was asserted at each swap above — the
        # commit dir is gone once the launcher exits)
        assert reg.current().manifest_seq >= corrupted_seq
        # the grow happened: the FINAL generation ran at np=3
        lines = [json.loads(l) for l in pout.splitlines()
                 if l.startswith("{")]
        assert len(lines) == 3 and all(l["size"] == 3 for l in lines)
    finally:
        if srv is not None:
            srv.close()
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)
