"""Tokens that reached clients during the window, over the window's
seconds and the cell's chips (host clock).  A token counts once, when the
client's view of its request first holds it: duplicate copies and redone
tokens do not count."""


def read(rec):
    return rec["delivered_tokens"] / rec["seconds"] / rec["chips"]
