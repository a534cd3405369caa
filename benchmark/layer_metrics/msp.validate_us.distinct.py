"""Mean microseconds of one certificate-chain validation over the
window (`msp_validate_seconds`, every MSP and every result: sum ÷
count).  None on a program that does not time them, or where the window
validated none."""
from account_readers import mean_ms


def read(obs):
    ms = mean_ms(obs, "msp_validate_seconds")
    return None if ms is None else 1e3 * ms
