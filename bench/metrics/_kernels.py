"""How the device trace tells the program's Pallas kernels apart.

The program gives its pallas_calls no `name=`, so the trace names a kernel
only by its HLO text: a `custom-call` with custom_call_target
"tpu_custom_call" and its operand and result shapes. The FourierFT ΔW
kernels are the two with two (1, N) int32 entry rows among their operands:
the forward maps an (L, 1, N) float32 coefficient stack to an (L, d1, d2)
float32 ΔW stack; the coefficient gradient maps an (L, d1, d2) cotangent
back to (L, 1, N). (The DCT kernels share these signatures; no cell runs
both methods.)
"""
_T = r"\{[^}]*\}"
_ENTRIES = rf"s32\[1,\d+\]{_T} \S+, s32\[1,\d+\]{_T} \S+\)"
FOURIER_DELTAW_FWD = (rf"= f32\[\d+,\d+,\d+\]{_T} custom-call\("
                      rf"f32\[\d+,1,\d+\]{_T} \S+, {_ENTRIES}"
                      r".*tpu_custom_call")
FOURIER_DELTAW_GRAD = (rf"= f32\[\d+,1,\d+\]{_T} custom-call\("
                       rf"f32\[\d+,\d+,\d+\]{_T} \S+, {_ENTRIES}"
                       r".*tpu_custom_call")
FOURIER_DELTAW = f"(?:{FOURIER_DELTAW_FWD})|(?:{FOURIER_DELTAW_GRAD})"

# paged attention: the one Pallas kernel of the serving decode step (the
# bank applies adapters with einsums); it maps the block table s32[B, P],
# the lengths s32[B], the queries and the two (pages, page_size, K, hd)
# bf16 pools to the attention output
_BF16_4D = rf"bf16\[\d+,\d+,\d+,\d+\]{_T} \S+"
PAGED_ATTENTION = (rf"= bf16\[\d+,\d+,\d+,\d+\]{_T} custom-call\("
                   rf"s32\[\d+,\d+\]{_T} \S+, s32\[\d+\]{_T} \S+, "
                   rf"{_BF16_4D}, {_BF16_4D}, {_BF16_4D}\)"
                   r".*tpu_custom_call")
