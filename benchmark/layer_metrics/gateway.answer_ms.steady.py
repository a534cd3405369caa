"""Mean wait of an answered transaction from its code held (or the
commit_status call's arrival, if later) to the reply:
gateway_commit_stage_seconds{stage="answer"}."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "gateway_commit_stage_seconds", stage="answer")
