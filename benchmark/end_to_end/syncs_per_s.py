"""Complete reconnect handshakes (disconnect, connect, client SyncStep1 ->
server SyncStep2 in hand) per second of window."""


def read(w):
    n = len(w.indices("reconnect"))
    return n / w.elapsed if n else None
