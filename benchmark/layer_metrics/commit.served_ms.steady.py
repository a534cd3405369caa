"""Mean wait of an answered transaction from committer.store_block
begun to its code held by the gateway's notifier (validate + commit of a
served block): gateway_commit_stage_seconds{stage="commit"}."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "gateway_commit_stage_seconds", stage="commit")
