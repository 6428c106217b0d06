from .rules import (DEFAULT_RULES, fsdp_rules, serve_rules, resolve,
                    tree_shardings, with_updates)
from .ctx import use_sharding, constrain, current
