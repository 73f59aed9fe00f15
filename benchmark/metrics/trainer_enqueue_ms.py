"""Host time per step inside the upload of the batch and the call to the
compiled step (the benchmark's own spans around them)."""


def read(facts):
    host = facts["host_seconds"]
    return 1e3 * (host.get("upload", 0.0) + host.get("enqueue", 0.0)) \
        / facts["steps"]
