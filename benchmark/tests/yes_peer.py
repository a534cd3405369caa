"""The control's device peer: `fabric_tpu.node.peer` with a verifier
that answers yes to everything, which breaks the guarantee that a
tampered envelope is flagged.  Started in the peer's place by the
`yes_verifier` fault; no benchmark run uses it."""

import sys

from benchmark.drivers.catchup_child import break_verifier
from fabric_tpu.node import peer


class YesPeer(peer.PeerNode):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        break_verifier(self.provider)


if __name__ == "__main__":
    peer.PeerNode = YesPeer
    sys.exit(peer.main())
