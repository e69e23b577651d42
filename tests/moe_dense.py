"""The dense, capacity-free form of a routed expert layer: every expert
runs on every token and a (T, E) matrix of weights, zero where an expert
was not chosen, picks what counts. O(T*E) compute and an (E, T, H) tensor:
what `parallel/moe.py` had as `_route_dense` before its one dropless
dispatch, kept as the tests' reference for it (chip_smoke.py's `moe`
phase has its own float32 loop over experts)."""
import jax
import jax.numpy as jnp


def dense_moe(tokens, top_vals, top_idx, w_up, w_down, act, w_gate=None):
    """Same contract as parallel.moe.dropless_moe."""
    num_experts = w_up.shape[0]
    oh = jax.nn.one_hot(top_idx, num_experts, dtype=top_vals.dtype)  # (T,k,E)
    combine = jnp.einsum("tk,tke->te", top_vals, oh)                 # (T,E)
    h = act(jnp.einsum("td,edh->eth", tokens, w_up)) if w_gate is None \
        else act(jnp.einsum("td,edh->eth", tokens, w_gate)) \
        * jnp.einsum("td,edh->eth", tokens, w_up)
    y = jnp.einsum("eth,ehd->etd", h, w_down)
    return jnp.einsum("etd,te->td", y, combine.astype(y.dtype))
