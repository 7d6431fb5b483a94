"""Bytes one worker puts on the wire in one outer sync, as the program
counts them (``Diloco.sync_wire_bytes``); None for a driver that does
not record it."""


def read(obs):
    return obs.get("wire_bytes_per_sync")
