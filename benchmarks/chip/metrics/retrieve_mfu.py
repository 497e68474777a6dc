"""The whole retrieval step's share of the chip over the window: the
least time every retrieval of the window needs at the roofline (the
bytes of ``counts.retrieval_bytes`` per real query over the HBM
bandwidth; retrieval is bound by bytes, not FLOPs) over the window's
seconds."""

from counts import retrieval_bytes


def read(r):
    s = r.window.stats
    b = r.config["bank"]
    hits, probes = s.get("probe_hits"), s.get("probes")
    if not probes:
        return None
    args = (b["slots"], b["max_locs"], b["hierarchy_n"])
    nbytes = (hits * retrieval_bytes(*args, True)
              + (probes - hits) * retrieval_bytes(*args, False))
    return 100.0 * nbytes / r.peak["hbm_bytes_per_s"] / r.window.seconds
