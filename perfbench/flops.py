"""Operations the algorithm requires, from shapes alone. Recomputed
operations (the flash backward's second pass over the scores, remat) do
not count. bench.py's arithmetic (6 N T + attention), with N the matmul
parameters only: embedding look-ups multiply nothing.

Only what every transformer shares is here. Which of a configuration's
keys are the units, the depth and the heads is the builder's knowledge:
builders/<name>.py has `model_flops_per_token(config, seq_len)` and
`attention_flops_per_token(config, seq_len)`, written with these helpers
or, for another architecture, without them.

A multiply-add is 2 operations; backward is twice forward, so a weight
used once costs 6 per token, and attention's two S x S products cost
12 S U per token per layer, half of that under a causal mask.
"""


def transformer_train_flops_per_token(units, inner, layers, head_params,
                                      seq_len, causal):
    """Forward + backward operations per trained token of a standard
    transformer: `layers` blocks of four U x U projections and a
    U x inner x U MLP, `head_params` weights in output heads applied at
    every position, attention over `seq_len` keys."""
    matmul_params = layers * (4 * units * units + 2 * units * inner) \
        + head_params
    return 6 * matmul_params + layers * attention_train_flops_per_token(
        units, seq_len, causal)


def attention_train_flops_per_token(units, seq_len, causal):
    """Forward + backward operations of Q K^T and P V for one token of one
    layer (all heads: heads x head_size = units)."""
    return (6 if causal else 12) * seq_len * units
