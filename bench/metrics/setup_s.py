"""Process start to the first timed frame: imports, device start, data,
compilation or cache load, and the warm-up of the cell's own shapes."""


def read(obs):
    return obs.setup_seconds
