"""The attention kernels' share of their roofline, compute-bound: the
least time the chip could take for the attention the algorithm requires
(forward + backward Q K^T and P V from the cell's shapes, the builder's
`attention_flops_per_token`; the backward's recomputation of the scores is
not counted), over the time the custom calls took. At S = 16k and D = 128
the kernels do 6 S U FLOP per token per layer against O(U) bytes, so peak
FLOP/s is the bound."""


def compute(context):
    trace = context["trace"]
    if trace is None or not trace["pallas_s"]:
        return None
    needed = context["attention_flops_per_token"] \
        * context["tokens_per_step"] * context["steps"] / context["chips"]
    least_s = needed / context["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / trace["pallas_s"]
