"""Seconds the host spent building the VP-tree and flattening it for the
beam traversal (``InfinityIndex.train_history["build_s"]``: tree +
flatten)."""


def read(run):
    b = run["build_s"]
    if "tree" not in b:
        return None
    return b["tree"] + b.get("flatten", 0.0)
