from repro.utils.misc import (GB, MB, ceil_div, enable_compilation_cache,
                              round_up, stable_hash, tree_bytes)
