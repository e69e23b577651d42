"""Real multi-process distributed tests (ref tests/nightly/dist_sync_kvstore.py:36-81).

Spawns worker processes on one host through tools/launch.py (the same code
path a user runs), each initialising jax.distributed on the CPU backend, and
asserts: cross-process push/pull aggregation, bitwise-identical params after
dist_sync training steps, and a global-mesh SPMD collective.
"""
import os
import re
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 3 workers, matching the reference nightly's shape
# (ref tests/nightly/dist_sync_kvstore.py:36-81: 3-worker sync/async
# x {none, 2bit} compression x {dense, row_sparse})
NWORKERS = 3


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_dist_matrix_three_processes():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # workers use 1 CPU device each
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", str(NWORKERS),
           "--coord-addr", "127.0.0.1:%d" % _free_port(),
           sys.executable, os.path.join(REPO, "tests", "dist_worker.py")]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=280)
    out = proc.stdout
    assert proc.returncode == 0, "workers failed:\n%s\n%s" % (out, proc.stderr)

    # workers share the stdout pipe, so lines may interleave — parse by regex
    # the tempered token stops a value at a glued "RESULT..." from another worker
    results = re.findall(r"RESULT (\w+) (\d+)(?: ((?:(?!RESULT)\S)+))?", out)
    for check in ("pushpull", "compress", "spmd", "rowsparse_sync", "done"):
        ranks = {r for c, r, _ in results if c == check}
        assert len(ranks) == NWORKERS, (check, out)

    digests = {r: v for c, r, v in results if c == "params"}
    assert len(digests) == NWORKERS, out
    assert len(set(digests.values())) == 1, \
        "params diverged across workers: %s" % digests

    # dist_async (bounded staleness): diverged after 1 local push,
    # reconverged after the staleness-triggered average, and again after
    # the forced sync()
    div = {r: v for c, r, v in results if c == "async_diverged"}
    syn = {r: v for c, r, v in results if c == "async_synced"}
    frc = {r: v for c, r, v in results if c == "async_forced"}
    assert len(div) == NWORKERS and len(syn) == NWORKERS \
        and len(frc) == NWORKERS, out
    assert len(set(div.values())) == NWORKERS, \
        "dist_async should diverge between averages: %s" % div
    assert len(set(syn.values())) == 1, \
        "dist_async diverged after averaging: %s" % syn
    assert len(set(frc.values())) == 1, \
        "dist_async diverged after forced sync: %s" % frc

    # uneven shards (worker 1 ran 2 fewer pushes): completed without
    # deadlock AND reconverged bitwise at the epoch-end sync
    unev = {r: v for c, r, v in results if c == "async_uneven"}
    assert len(unev) == NWORKERS, out
    assert len(set(unev.values())) == 1, \
        "dist_async diverged after uneven epoch: %s" % unev

    # Module update-on-kvstore: different per-worker data, identical
    # updated params (grads aggregated through the dist store)
    mkv = {r: v for c, r, v in results if c == "module_kv"}
    assert len(mkv) == NWORKERS, out
    assert len(set(mkv.values())) == 1, \
        "Module update-on-kvstore diverged: %s" % mkv

    # row_sparse x dist_async: every worker converged to the same average
    rsa = {r: v for c, r, v in results if c == "rowsparse_async"}
    assert len(rsa) == NWORKERS, out
    assert len(set(rsa.values())) == 1, \
        "dist_async row_sparse diverged after sync: %s" % rsa
