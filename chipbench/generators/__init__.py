"""Traffic generators, one module per kind of loop.

A traffic file ``traffic/<mix>.json`` names its loop under ``"loop"``;
the harness imports ``generators/<loop>.py`` and calls its ``run``.
Every length and gap comes from :mod:`.lengths`: each seed draws the
same multiset of sizes and arrivals, in its own order, so seeds differ in
order and token ids and not in the amount of work.
"""
