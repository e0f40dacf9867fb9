"""Share of the window's prompt tokens served from the prefix cache:
hit tokens over hit tokens plus prompt tokens computed, % (engine
counters). Nothing to read where the engine has the cache off."""


def read(data):
    if data["kind"] != "serve":
        return None
    k = data["counters"]
    hit, computed = k["prefix_hit_tokens"], k["prefill_tokens"]
    if not k["prefix_enabled"] or hit + computed == 0:
        return None
    return 100.0 * hit / (hit + computed)
