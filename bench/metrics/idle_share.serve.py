"""Device idle share of the traced serving segment: 1 - busy / window, %."""


def read(data):
    tr = data.get("trace")
    if data["kind"] != "serve" or tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
