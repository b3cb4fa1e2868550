"""Seconds spent on the sparse q-metric projection and on fitting the
embedding Phi (``InfinityIndex.train_history["build_s"]``: projection +
phi)."""


def read(run):
    b = run["build_s"]
    if "phi" not in b:
        return None
    return b.get("projection", 0.0) + b["phi"]
