"""Lowerings of traced programs to MLIR per whole fit in the window
(JAX's ``jaxpr_to_mlir_module`` events during the window's own
``DTSVM.fit`` calls; a persistent-cache hit still counts).  A fit that
keeps its compiled programs lowers nothing."""


def read(r):
    whole = r.result.get("whole")
    if not whole or not whole["fits"]:
        return None
    return whole["lowerings"] / whole["fits"]
