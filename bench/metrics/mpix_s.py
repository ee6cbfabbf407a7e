"""Output megapixels completed inside the window, over its seconds."""


def read(obs):
    return obs.pixels_done / obs.window_s / 1e6
