"""A value the run observed, by its path in `observed`."""


def read(observed, path, scale=1.0):
    node = observed
    for key in path:
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return node * scale
