"""Process start to the first measured instant: imports, weights, engine
build, compile or cache load, warm-up and, in training, the reference check."""


def value(window, cell):
    return cell["setup_s"]
