"""Placed window bytes of every packed projection over their logical
bit-plane bytes, from the packs' shapes (1.0 means no window padding)."""


def read(ctx):
    packs = ctx.get("packs")
    if not packs or not packs["logical_bytes"]:
        return None
    return packs["placed_bytes"] / packs["logical_bytes"]
