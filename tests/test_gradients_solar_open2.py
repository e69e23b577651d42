"""Solar Open 2's checked gradients against the float32 reference the
benchmark uses, with and without per-layer recomputation and with both
kinds of kernel interpreted. Part of tests/test_solar_open2.py up to the
review of PR 48, where it made that file the last to end in the tier-1 run
(517 s of one worker under `--dist loadfile`); a file of its own, early in
the alphabet, is taken by another worker. The helpers are that file's.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu import models, nd
from incubator_mxnet_tpu.gluon import utils as gutils
from incubator_mxnet_tpu.ops import attention
from incubator_mxnet_tpu.ops import delta_rule as rule_mod

from test_solar_open2 import CFG, HELD, S, batch, build, builder, reference


def _loss_and_grads(net, tokens, labels):
    params = [p for _, p in sorted(net.collect_params().items())
              if p.grad_req != "null"]
    loss_fn = models.ChunkedUntiedLMLoss(net)

    def fn(datas):
        arrs = [p.data() for p in params]
        saved = [a._data for a in arrs]
        for a, d in zip(arrs, datas):
            a._data = d
        try:
            out = loss_fn(net.features(nd.array(tokens)), nd.array(labels))
        finally:
            for a, s in zip(arrs, saved):
                a._data = s
        return out._data.sum()

    grads = jax.grad(fn)([p.data()._data for p in params])
    return dict(zip([p.name for p in params], grads))


#: heads of 128 at 128 positions: the delta rule's kernel pair and, in the
#: `G` layer, the streamed attention kernels
KERNEL_CFG = dict(CFG, head_dim=128, hidden_size=128, linear_attn_config=dict(
    CFG["linear_attn_config"], head_dim=128))


@pytest.mark.parametrize("remat,cfg,s", [
    (False, CFG, S), (True, CFG, S), (True, KERNEL_CFG, 128)],
    ids=["stored", "recomputed", "recomputed_kernels"])
def test_gradients_match_the_reference(monkeypatch, remat, cfg, s):
    """Every checked parameter's gradient (the last K layer's A_log,
    dt_bias, both rank -> heads x d maps and the in-projection by its rows;
    the G layer's gate; the last layer's router, shared expert and held
    experts) against the reference's, float32 at "highest", with and
    without per-layer recomputation: 1e-4 of each gradient's largest entry
    (summation order through three layers and the head). The last case:
    both kinds of kernel (interpreted), every layer recomputed but for
    what they wrote (`solar_open2._KEPT`): o and the chunks' states, o and
    lse of the first forward beside operands made again."""
    kernels = cfg is KERNEL_CFG
    if kernels:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    ran = (rule_mod._CALLS.value(path="pallas"),
           attention._ROUTES.value(route="streamed"),
           gutils._RECOMPUTES.value(policy="given"))
    net = build(cfg, remat=remat, attention="flash" if kernels else "dense")
    tokens, labels = batch(cfg=cfg, s=s)
    with jax.default_matmul_precision("highest"):
        # (compiled whole: op by op the reference is several times slower)
        want = jax.jit(lambda p: reference.checked_grads(
            p, cfg, jnp.asarray(tokens), jnp.asarray(labels)))(
                builder.reference_params(net))
        got = _loss_and_grads(net, tokens, labels)
    assert (rule_mod._CALLS.value(path="pallas") - ran[0],
            attention._ROUTES.value(route="streamed") > ran[1],
            gutils._RECOMPUTES.value(policy="given") - ran[2]) \
        == (2 * kernels, kernels, 3 * remat)
    k, e = net.layers[2].mixer, net.layers[2].experts
    rows = onp.split(onp.asarray(got[k.in_proj.weight.name]),
                     reference._in_proj_rows(
                         builder.reference_params(net)["layers"][2]), 0)
    mine = {"kda_A_log": got[k.A_log.name],
            "kda_dt_bias": got[k.dt_bias.name],
            "kda_decay_up": got[k.decay_up.name],
            "kda_gate_up": got[k.gate_up.name],
            "gqa_gate": got[net.layers[0].mixer.gate.weight.name],
            "moe_router": got[e.moe.gate_weight.name],
            "moe_shared_gate_up": got[e.shared.gate_up.weight.name],
            "moe_shared_down": got[e.shared.down.weight.name]}
    mine.update(zip(("kda_" + n for n in reference.KDA_ROWS), rows))
    mine.update({"moe_%s_e%d" % (n, i): got[p.name][i]
                 for n, p in (("w1", e.moe.w1), ("w2", e.moe.w2),
                              ("w3", e.moe.w3)) for i in range(HELD)})
    assert set(want) == set(mine)
    assert onp.asarray(want["kda_beta"]).shape == (2, cfg["hidden_size"])
    for name in want:
        w, g = onp.asarray(want[name]), onp.asarray(mine[name])
        assert onp.abs(g - w).max() < 1e-4 * onp.abs(w).max(), name
        assert onp.abs(w).max() > 0, name
