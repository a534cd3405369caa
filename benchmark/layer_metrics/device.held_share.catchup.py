"""Share of the window the device was held by observed dispatches."""
from account_readers import held_share as read
