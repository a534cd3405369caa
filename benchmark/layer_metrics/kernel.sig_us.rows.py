"""Device time of the fixed-comb rows program per real signature."""
from readers import kernel_sig_us


def read(obs):
    return kernel_sig_us(obs, "rows")
