"""Share of device-busy time in ops under the scope `router` of a
`SharedExpertMoE` block: the 512 sigmoid scores, the group choice
(`router_groups`), the top-k, the chosen scores and the balancing rule;
forward, recomputed forward and backward."""
import latent_shares  # perfbench/latent_shares.py: run.py's directory is on sys.path


def compute(context):
    return latent_shares.share_of_busy(context, "group_router")
