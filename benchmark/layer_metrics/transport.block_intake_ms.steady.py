"""Mean wait of an answered transaction from its block's frame received
by the device peer to committer.store_block begun (the block's signature
dispatch, the byzantine check, the gossip forward):
gateway_commit_stage_seconds{stage="intake"}."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "gateway_commit_stage_seconds", stage="intake")
