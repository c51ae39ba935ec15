from repro.kvcache.cache import (  # noqa: F401
    KVCache, abstract_kv_cache, append_token, append_token_stacked,
    init_kv_cache, read_slot, write_prefix, write_slot_prefix,
)
from repro.kvcache.block_table import (  # noqa: F401
    NULL_BLOCK, SlotTables, blocks_for, validate_block_size,
)
from repro.kvcache.paged import (  # noqa: F401
    BlockPool, HostBlockPool, PagedKVCache, PoolExhausted, append_layer,
    copy_block, extract_blocks, gather_layer, grow_paged_kv_cache,
    init_paged_kv_cache, insert_blocks, write_blocks,
)
from repro.kvcache.transfer import PrefetchEngine  # noqa: F401
