"""Seeded-defect canary artifacts — the lint-the-linter fixture.

``write_canary(dir)`` exports two small but REAL v2 AOT artifacts with
known compiled-program defects, byte-compatible with aot.py's format
(magic + header imported from there, never re-derived):

- ``serve-…``: an fp64 elementwise program — must fire **H001** (x64
  leak on the serving path) and nothing else,
- ``train-…``: an SGD-shaped ``w - 0.1*g`` module exported WITHOUT
  donate_argnums — must fire **H002** (zero input-output aliasing) and
  nothing else.

ci/run.sh's hlolint stage regenerates these per run and hard-fails
unless the scan reports exactly {H001, H002}: the H-passes can never
silently rot (the same discipline as mxtpulint's seeded_defects.py).
Generated, not committed: a serialized jax.export payload is pinned to
the jax version, and the canary must keep proving the REAL deserialize
path works on the running toolchain.

``write_decode_canary(dir)`` is the generative-serving sibling: one
``decode-…`` artifact exported WITHOUT pool donation — must fire **H002**
at ERROR severity (the path-aware escalation serving/generate.py's load
gate relies on). It writes to its own directory and is exercised by
ci/run.sh's generate stage, so the base canary's exact-{H001, H002}
assertion stays byte-stable.

``write_diff_canaries(dir)`` is tools/hlodiff's lint-the-differ set:
five seeded REGRESSION PAIRS (``<name>/base`` + ``<name>/cand`` per
pair), each of which must diff to exactly one D-rule — FLOPs-regressed
header (D001), donation dropped across the pair (D003), bf16 program
widened to f32 (D004), a collective gained on the dispatch path (D005),
and a shrunk bucket ladder (D006). ci/run.sh's hlodiff stage asserts
each pair's exact rule set, same discipline as the H canaries above.

CLI: ``python -m tools.hlolint.canary OUT_DIR``.
"""
from __future__ import annotations

import hashlib
import os
import sys

__all__ = ["write_canary", "write_decode_canary", "write_diff_canaries"]


def write_canary(out_dir):
    """Write the two seeded artifacts under ``out_dir`` (the same
    jax-<version>/ layout aot.py uses); returns their paths."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export
    from incubator_mxnet_tpu import aot
    from incubator_mxnet_tpu.base import enable_x64

    with enable_x64():
        # H001: a serve program that computes in fp64 end to end
        exp_f64 = jax_export.export(jax.jit(lambda x: x * 2.0))(
            jax.ShapeDtypeStruct((8,), jnp.float64))

    def step(w, g):
        return w - 0.1 * g

    spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    # H002: a train module with NO donate_argnums -> zero aliased buffers
    exp_train = jax_export.export(jax.jit(step))(spec, spec)

    ver_dir = os.path.join(out_dir, "jax-%s" % jax.__version__)
    os.makedirs(ver_dir, exist_ok=True)
    paths = []
    for kind, exported in (("serve", exp_f64), ("train", exp_train)):
        payload = bytes(exported.serialize())
        digest = hashlib.sha256(payload).hexdigest()[:32]
        path = os.path.join(ver_dir, "%s-%s.mxtpu-aot" % (kind, digest))
        with open(path, "wb") as f:
            f.write(aot.ARTIFACT_MAGIC + aot._pack_header(None) + payload)
        paths.append(path)
    return paths


def write_decode_canary(out_dir):
    """Write one seeded DECODE artifact under ``out_dir``: a KV-pool
    update step exported without donate_argnums, so its module aliases
    zero inputs — the H002-at-error-severity fixture for the generative
    load gate. Returns the artifact path."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export
    from incubator_mxnet_tpu import aot

    def step(pool, k):
        # the donation-missing shape of serving/generate.py's decode
        # step: pool in, updated pool out — but NOT donated
        return pool.at[0].set(k), jnp.argmax(k)

    exp = jax_export.export(jax.jit(step))(
        jax.ShapeDtypeStruct((16, 8, 4), jnp.float32),
        jax.ShapeDtypeStruct((8, 4), jnp.float32))
    ver_dir = os.path.join(out_dir, "jax-%s" % jax.__version__)
    os.makedirs(ver_dir, exist_ok=True)
    payload = bytes(exp.serialize())
    digest = hashlib.sha256(payload).hexdigest()[:32]
    path = os.path.join(ver_dir, "decode-%s.mxtpu-aot" % digest)
    with open(path, "wb") as f:
        f.write(aot.ARTIFACT_MAGIC + aot._pack_header(None) + payload)
    return path


def _write_artifact(out_dir, kind, exported, stats=None):
    """One v2 artifact under ``out_dir`` in aot.py's layout (magic +
    header imported, never re-derived) — the digest covers the payload,
    so two canaries that differ only in header stats still get distinct
    file bytes (and therefore distinct ``aot.program_digest``s: the
    hlodiff byte-identical short-circuit must not eat them)."""
    import jax
    from incubator_mxnet_tpu import aot
    ver_dir = os.path.join(out_dir, "jax-%s" % jax.__version__)
    os.makedirs(ver_dir, exist_ok=True)
    payload = bytes(exported.serialize())
    digest = hashlib.sha256(payload).hexdigest()[:32]
    path = os.path.join(ver_dir, "%s-%s.mxtpu-aot" % (kind, digest))
    with open(path, "wb") as f:
        f.write(aot.ARTIFACT_MAGIC + aot._pack_header(stats) + payload)
    return path


def write_diff_canaries(out_dir):
    """Write the five hlodiff regression pairs. Returns a dict
    ``name -> (base_dir, cand_dir, expected_rule_set)`` where diffing
    ``cand`` against ``base`` must yield EXACTLY ``expected_rule_set``
    (as the set of distinct rule ids) — the differ-the-differ fixture
    ci/run.sh's hlodiff stage regenerates and asserts per run."""
    import numpy as onp
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export
    from jax.sharding import Mesh, PartitionSpec as P

    def exp(fn, *specs):
        return jax_export.export(jax.jit(fn))(*specs)

    f32_84 = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    f32_48 = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    f32_164 = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    bf16_84 = jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)
    pairs = {}

    def dirs(name):
        b = os.path.join(out_dir, name, "base")
        c = os.path.join(out_dir, name, "cand")
        return b, c

    # D001: byte-identical PROGRAM, regressed header cost facts — the
    # candidate claims 2x the FLOPs of the base it replaces (serve kind,
    # so the finding lands at error severity: the deploy-gate shape)
    b, c = dirs("flops")
    same = exp(lambda x: x * 2.0, f32_84)
    _write_artifact(b, "serve", same, stats={"flops": 1.0e6})
    _write_artifact(c, "serve", same, stats={"flops": 2.0e6})
    pairs["flops"] = (b, c, {"D001"})

    # D003: the base donated its accumulator arg, the candidate's
    # re-export silently lost donate_argnums (serve kind -> error)
    b, c = dirs("donation")
    def step(w, g):
        return w - 0.1 * g
    _write_artifact(b, "serve",
                    jax_export.export(jax.jit(step, donate_argnums=(0,)))(
                        f32_48, f32_48))
    _write_artifact(c, "serve", exp(step, f32_48, f32_48))
    pairs["donation"] = (b, c, {"D003"})

    # D004: the same eval program re-exported with its working dtype
    # widened bf16 -> f32 (2x the HBM traffic per op site)
    b, c = dirs("widened")
    _write_artifact(b, "eval", exp(lambda x: x * x + x, bf16_84))
    _write_artifact(c, "eval", exp(lambda x: x * x + x, f32_84))
    pairs["widened"] = (b, c, {"D004"})

    # D005: the candidate's partitioning grew an all_gather the base's
    # dispatch path never paid (1-device mesh still EXPORTS the
    # collective op; check_vma=False keeps the replication checker out
    # of the single-device canary)
    b, c = dirs("collective")
    _write_artifact(b, "decode", exp(lambda x: x + 1.0, f32_84))
    mesh = Mesh(onp.array(jax.devices()[:1]), ("x",))
    gathered = jax.shard_map(
        lambda x: jax.lax.all_gather(x, "x", tiled=True),
        mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False)
    _write_artifact(c, "decode", exp(gathered, f32_84))
    pairs["collective"] = (b, c, {"D005"})

    # D006: the candidate ladder LOST bucket 16 — requests that sized
    # into it now pad up or compile after cutover (the cand-8 program
    # uses a different constant so the byte-identical short-circuit
    # doesn't drop the surviving bucket before the set rule runs)
    b, c = dirs("ladder")
    _write_artifact(b, "eval", exp(lambda x: x + 1.0, f32_84))
    _write_artifact(b, "eval", exp(lambda x: x + 1.0, f32_164))
    _write_artifact(c, "eval", exp(lambda x: x + 2.0, f32_84))
    pairs["ladder"] = (b, c, {"D006"})
    return pairs


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m tools.hlolint.canary OUT_DIR",
              file=sys.stderr)
        return 2
    for path in write_canary(argv[0]):
        print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
