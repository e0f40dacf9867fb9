"""Host milliseconds per engine tick: the harness's ``engine_step`` span
minus the device's busy time inside it, over the traced ticks."""


def read(data):
    tr = data.get("trace")
    if data["kind"] != "serve" or tr is None:
        return None
    n, host_s = tr.self_time("engine_step")
    return 1e3 * host_s / n if n else None
