"""Every byte the simulated network carried in the window (frames of
deltas, acks and retransmits; the simulator's own byte counter) over the
updates made visible everywhere in the window. A change that buys
latency by shipping more state shows here."""


def read(rec, ctx):
    n = rec.counts.get("updates_visible_in_window", 0)
    if not n or "wire_bytes" not in rec.counts:
        return None
    return rec.counts["wire_bytes"] / n
