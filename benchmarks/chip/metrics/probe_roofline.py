"""The probe kernel's share of its roofline: the least time the window's
probes need (the bytes of ``counts.probe_kernel_bytes`` for every real
query over the chip's HBM bandwidth; the probe does no arithmetic worth
counting) over the summed device time of the kernel's events
(``cuckoo_probe``) in the trace."""

from counts import probe_kernel_bytes

KERNEL = "cuckoo_probe"


def read(r):
    t = r.trace
    if t is None or not t.kernel_events.get(KERNEL):
        return None
    s = r.window.stats
    slots = r.config["bank"]["slots"]
    hits, probes = s.get("probe_hits"), s.get("probes")
    if not probes:
        return None
    nbytes = (hits * probe_kernel_bytes(slots, True)
              + (probes - hits) * probe_kernel_bytes(slots, False))
    least = nbytes / r.peak["hbm_bytes_per_s"]
    return 100.0 * least / t.kernel_s[KERNEL]
