"""Recurrent state (Mamba ``ssm`` and ``conv`` leaves) that the KV store
put and fetched in the window (``KvStoreStats.put_recurrent_bytes`` +
``fetch_recurrent_bytes``), in MB (10^6) per completed op. None where
the program does not count it."""


def read(run):
    put = run.counters.get("kv_put_recurrent_bytes")
    fetch = run.counters.get("kv_fetch_recurrent_bytes")
    if put is None or fetch is None or not run.done:
        return None
    return (put + fetch) / 1e6 / len(run.done)
