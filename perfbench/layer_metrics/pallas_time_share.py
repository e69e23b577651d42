"""Share of device-busy time in custom calls: these programs have no
custom call but the three Pallas attention kernels."""


def compute(context):
    trace = context["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
