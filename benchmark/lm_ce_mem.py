"""Peak-HBM A/B of dense vs chunked LM cross-entropy on the real chip.

The committed numbers in docs/PERF_BERT.md "Chunked CE: measured peak
memory" come from here. Each variant runs value_and_grad at T=32k tokens,
U=1024, V=32k (fp32 logits block = 4 GB) in its OWN subprocess so PJRT's
peak_bytes_in_use counter reflects exactly one variant.

Usage: python benchmark/lm_ce_mem.py          # runs both, prints JSON
       python benchmark/lm_ce_mem.py dense    # one variant (subprocess)
"""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T, U, V = 32768, 1024, 32768


def run_variant(name):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.lm_ce import chunked_lm_cross_entropy

    h = jax.random.normal(jax.random.PRNGKey(0), (T, U), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (V, U), jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)

    def dense(h, w, y):
        logits = (h @ w.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - lab)

    def chunked(h, w, y):
        return jnp.mean(chunked_lm_cross_entropy(h, w, y))  # auto chunks

    fn = {"dense": dense, "chunked": chunked}[name]
    g = jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))
    # primary metric: compiled temp buffer (exact, deterministic)
    ma = g.lower(h, w, y).compile().memory_analysis()
    out = g(h, w, y)
    jax.block_until_ready(out)
    # runtime peak via a LIVE devstats sample (sample_now polls the
    # devices right here, after block_until_ready — device_memory()
    # would return the daemon's cached snapshot when a sampler runs,
    # which can predate this variant's high-water mark); it degrades to
    # host-RSS report-only samples on backends with no PJRT memory_stats
    # instead of this script hand-rolling a fallback
    from incubator_mxnet_tpu.telemetry import devstats
    peak = max((s.get("peak_bytes_in_use", 0)
                for s in devstats.sample_now().values()), default=0)
    print(json.dumps({
        "variant": name, "loss": float(out[0]),
        "temp_gb": round(ma.temp_size_in_bytes / 2 ** 30, 2),
        "peak_gb": round(peak / 2 ** 30, 2)}))


def main():
    if len(sys.argv) > 1:
        run_variant(sys.argv[1])
        return
    results = {}
    for name in ("dense", "chunked"):
        r = subprocess.run([sys.executable, os.path.abspath(__file__), name],
                           capture_output=True, text=True, timeout=900)
        line = [l for l in r.stdout.splitlines() if l.startswith("{")]
        if r.returncode or not line:
            results[name] = {"error": (r.stdout + r.stderr)[-400:]}
        else:
            results[name] = json.loads(line[-1])
    d, c = results.get("dense", {}), results.get("chunked", {})
    if "temp_gb" in d and "temp_gb" in c:
        results["temp_drop_gb"] = round(d["temp_gb"] - c["temp_gb"], 2)
        results["logits_block_gb"] = round(T * V * 4 / 2 ** 30, 2)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
