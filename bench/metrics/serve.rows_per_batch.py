"""Rows per GEMM batch in the window, from the ``rows`` and ``batches``
counters of ``PredictServer.stats()``."""


def read(r):
    srv = r.result.get("server")
    if not srv or not srv["batches"]:
        return None
    return srv["rows"] / srv["batches"]
