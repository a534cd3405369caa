"""Median per block of the span `validator.identities`: resolving a
block's unique creators and endorsers through the channel's MSPs
(deserialise; for a creator, validate its certificate chain) — on the
deep tail the stretch between the C walk and `assemble`.  None where the
run kept no such span: untraced, or a program without it."""
from readers import block_ms


def read(obs):
    return block_ms(obs, ("validator.identities",))
