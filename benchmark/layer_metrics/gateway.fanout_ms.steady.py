"""Fan-out time per endorse verb (dial + handshake + call, every target
peer): span gateway.fanout over the gateway's endorse count."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "span_duration_seconds", span="gateway.fanout",
                   per=("gateway_request_duration_seconds_count",
                        {"verb": "endorse"}))
