"""KV bytes the engine holds on the device over the most KV that running
requests needed at once in the window (their context tokens times the KV
bytes of one token)."""


def read(run):
    live = [n for t, n in run.live_kv if run.in_window(t)]
    if not live or not max(live):
        return None
    return run.kv_held_bytes / (max(live) * run.dims.kv_bytes_per_token())
