from chipbench.layer_tools import prefill_ms_per_ktok as read  # prefill programs: a prefilling tick less a decode-only tick, per 1000 prompt tokens
