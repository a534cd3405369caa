"""1 - union of device-op intervals / traced window."""
from readers import idle_share_pct as read  # noqa: F401
