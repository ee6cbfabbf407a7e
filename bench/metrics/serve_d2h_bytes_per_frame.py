"""Bytes the serving engine copied from the device per completed frame
over the window (``engine.stats()`` ``d2h_bytes`` / ``completed``; the
padded planes of a light wave are copied out too)."""


def read(obs):
    eng = obs.engine
    if not eng or not eng.get("completed") or "d2h_bytes" not in eng:
        return None
    return eng["d2h_bytes"] / eng["completed"]


def describe(obs) -> str:
    eng = obs.engine
    return (f"d2h_bytes={eng['d2h_bytes']} h2d_bytes={eng.get('h2d_bytes')}"
            f" completed={eng['completed']} waves={eng.get('waves')}")
