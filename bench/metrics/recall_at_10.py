"""End to end: recall@10 of every answered query in the window against the
plain reference's exact top 10 on the same data."""


def read(rec):
    return rec["numbers"]["recall_at_10"]
