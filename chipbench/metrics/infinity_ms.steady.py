"""Device ms per dispatched batch of the infinity engine's programs (Phi
embedding, VP-tree traversal, rerank), from the device trace: every XLA
module of the traced window over the batches the server dispatched in it.
The steady cell's share of the quantity, which moves its own end-to-end
metric."""
from chipbench.layers import device_ms_per_batch

#: every module: in the window only the engine launches device programs
#: (the runtime and the server's padding run on the host), and its embed
#: and rerank run op by op, as modules named after each operation
MODULES = (r".*",)


def read(run):
    return device_ms_per_batch(run, MODULES)
