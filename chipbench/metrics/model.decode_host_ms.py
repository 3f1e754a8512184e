"""Host time of a decode round outside the paged decode call, per
router round: (``service.decode_round`` - ``decode.step``) total over
the count of ``router.round`` spans, in ms.  It covers the round's
table preparation, density accumulation, sampling and token
bookkeeping.  The program's own spans, read from ``router.stats()``; a
program without them gives nothing."""


def read(obs):
    tel = obs["router"].get("telemetry")
    if not tel:
        return None
    spans = tel["spans"]
    rounds = spans.get("router.round", {}).get("count", 0)
    if not rounds or "decode.step" not in spans:
        return None
    host = (spans["service.decode_round"]["total_s"]
            - spans["decode.step"]["total_s"])
    return 1e3 * host / rounds
