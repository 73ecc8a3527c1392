"""Host copies per byte moved over the window, from the store's own
counters: transport, client and media copies plus the staging bounce,
over the transport's bytes moved."""


def read(run):
    c = run.counters
    moved = c.get("transport.bytes_moved", 0)
    if moved <= 0:
        return None
    copies = (c.get("transport.copy_bytes", 0)
              + c.get("client.host_copy_bytes", 0)
              + c.get("media.host_copy_bytes", 0)
              + c.get("staging.bounce_bytes", 0))
    return copies / moved
