"""Device milliseconds per execution of the engine's jitted mixed step
(``jit__step_fn``) in the traced segment."""


def read(data):
    tr = data.get("trace")
    if data["kind"] != "serve" or tr is None:
        return None
    n, sec = tr.module_time("jit__step_fn")
    return 1e3 * sec / n if n else None
