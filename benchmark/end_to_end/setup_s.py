"""Process start to the stamp that opens the window: build (first run in
a checkout), imports, backend, weights, compile or cache load, warm-up."""


def read(facts):
    return facts["setup_s"]
