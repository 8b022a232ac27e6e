from repro.common.platform import (DEVICE_PROFILES, PROFILES, TPU_V5E, VCK190,
                                   PlatformProfile, device_profile,
                                   get_profile)

__all__ = [
    "DEVICE_PROFILES",
    "PROFILES",
    "TPU_V5E",
    "VCK190",
    "PlatformProfile",
    "device_profile",
    "get_profile",
]
