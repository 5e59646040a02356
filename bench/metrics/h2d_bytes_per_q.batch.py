"""Bytes of dense query batches the program's plan cache uploaded from
the host in a closed batch window (``PlanCache.upload_bytes``, counted
before bucket padding), per completed query."""


def read(r):
    if r.get("loop") != "closed" or r.get("upload_bytes") is None or not r.get("completed"):
        return None
    return r["upload_bytes"] / r["completed"]
