"""A count the run took over the window: `observed["counters"][name]`."""


def read(observed, name):
    return observed.get("counters", {}).get(name)
