"""Mean time a dispatch waited for the chip behind earlier ones, all
lanes and sites."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "provider_dispatch_queue_wait_seconds")
