"""Device-resident query plane: batched gather/merge over window stacks."""
from .engine import (KEY_BUCKET_MIN, KEY_CHUNK,  # noqa: F401
                     fleet_window_query_device, fleet_window_query_paths,
                     key_bucket, key_chunk, shard_padded_rows,
                     um_gsum_device, um_window_query_device)
