"""Output tokens per engine dispatch over the window: ``engine.stats()``
after minus before (``tokens_emitted`` over ``dispatches``)."""


def read(run):
    delta = run["counters"].get("engine_delta")
    if not delta or not delta["dispatches"]:
        return None
    return delta["tokens_emitted"] / delta["dispatches"]
