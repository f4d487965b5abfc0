"""One number the run recorded as it is. args: ``path`` (keys into the
record), ``scale`` (optional factor)."""


def read(record, path, scale=1.0):
    node = record
    for key in path:
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return {"value": node * scale}
