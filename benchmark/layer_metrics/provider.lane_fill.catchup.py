"""1 - pad slots / lane slots over the window, from the child that holds the chip."""
from readers import lane_fill_pct as read  # noqa: F401
