"""KV bytes the engine holds on the device over the most KV that running
requests needed at once in the window (the family's yardstick on their
contexts)."""


def read(run):
    live = [run.yardstick.kv_bytes(ctx) for t, ctx in run.live_kv
            if run.in_window(t)]
    if not live or not max(live):
        return None
    return run.kv_held_bytes / max(live)
