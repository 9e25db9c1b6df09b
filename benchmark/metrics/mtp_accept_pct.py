"""Drafts accepted over drafts verified in the window, from the counters
the program keeps on the device (`cache.counters["mtp"]`, read at the
window's two ends): the share of decode iterations and lanes in which the
multi-token-prediction module's draft was the main model's own token, so
that the iteration yielded two tokens for that lane. With seeded weights
it reads near 0 (about one in the vocabulary's size): the cell then
measures what drafting COSTS."""


def read(run):
    drafted = run["counters"].get("mtp_drafted")
    if not drafted:
        return None
    return 100.0 * run["counters"]["mtp_accepted"] / drafted
