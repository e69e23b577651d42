"""memory_stats()["peak_bytes_in_use"] after the window, on the fullest
chip, set-up's reference check included."""


def compute(context):
    return context["peak_bytes"] / 2 ** 30 if context["peak_bytes"] else None
