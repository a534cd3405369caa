"""Mean wait of an answered transaction from the orderer's 200 to the
block that holds it received by the device peer (batch fill, consensus,
block write, deliver): gateway_commit_stage_seconds{stage="ordered"}."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "gateway_commit_stage_seconds", stage="ordered")
