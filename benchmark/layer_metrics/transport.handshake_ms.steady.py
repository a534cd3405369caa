"""Mean connection handshake recorded on the device peer: span
comm.handshake (the fan-out's dials; a responder has no context)."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "span_duration_seconds", span="comm.handshake")
