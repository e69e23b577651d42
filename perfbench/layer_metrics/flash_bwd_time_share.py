"""Share of device-busy time in the two backward Pallas kernels, found by
the names `ops/attention.py` gives them (`flash_bwd_dkv`, `flash_bwd_dq`);
`pallas_time_share` less this is the forward kernel's (`flash_fwd`)."""
import scope_shares  # perfbench/scope_shares.py: run.py's directory is on sys.path


def compute(context):
    return scope_shares.flash_bwd_share(context)
