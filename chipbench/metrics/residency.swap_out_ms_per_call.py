"""Host time of swap-out per finished call: (``residency.swap_out`` +
``residency.reclaim``) total over the ``calls_finished`` counter, in
ms.  It covers re-encoding, the page re-admissions at swap-out, the
AoT write submissions and the reclaim that follows.  The program's own
spans, read from ``router.stats()``; a program without them gives
nothing."""


def read(obs):
    tel = obs["router"].get("telemetry")
    if not tel:
        return None
    calls = tel["counters"].get("calls_finished", 0)
    if not calls:
        return None
    spans = tel["spans"]
    t = sum(spans.get(n, {}).get("total_s", 0.0)
            for n in ("residency.swap_out", "residency.reclaim"))
    return 1e3 * t / calls
