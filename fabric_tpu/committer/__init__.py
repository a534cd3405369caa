from .txvalidator import TxValidator, PolicyRegistry, ValidationResult
from .committer import Committer

__all__ = ["TxValidator", "PolicyRegistry", "ValidationResult", "Committer"]
