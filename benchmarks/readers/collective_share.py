"""Device time in collective operations over device-busy time, in per
cent. Summed durations: an async collective that runs under a fusion still
counts its whole length, so this is an upper bound on what is exposed."""


def read(context: dict):
    trace = context["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["collective_s"] / trace["busy_s"]
