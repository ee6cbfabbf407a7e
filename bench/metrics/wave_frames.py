"""Frames per dispatched wave over the window (``engine.stats()``
``completed`` / ``waves``)."""


def read(obs):
    eng = obs.engine
    if not eng or not eng.get("waves"):
        return None
    return eng["completed"] / eng["waves"]
