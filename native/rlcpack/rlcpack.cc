// Native packer for the ed25519 RLC batch equation: everything
// cometbft_tpu/crypto/ed25519.pack_rlc does for each signature, in one
// call a batch and outside the interpreter.
//
//   h_i = SHA512(R_i || A_i || M_i) mod L
//   z_i = the caller's 16 random bytes, little-endian, top bit set
//   slot(A) += z_i * h_i   (mod L; slots in first-appearance order)
//   c       += z_i * s_i   (mod L; rides in slot 0 with -B)
//   signed radix-32 digits of the slot scalars (52) and of z_i (26)
//
// The six arrays written are bit for bit what the Python packer
// returns for the same random bytes (tests/test_rlcpack.py pins it).
// The SHA-512 is this library's own, as native/bls12381/sha256.h is
// that library's.  No randomness is drawn here and no state outlives a
// call: two threads may pack at once.  Little-endian hosts only (the
// loader's self-test fails elsewhere and the Python packer serves).
#include <cstdint>
#include <cstdlib>
#include <cstring>

namespace {

typedef uint64_t u64;
typedef unsigned __int128 u128;

// -- SHA-512 (FIPS 180-4) ----------------------------------------------

const u64 K512[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL};

inline u64 rotr(u64 x, int n) { return (x >> n) | (x << (64 - n)); }

inline u64 load_be64(const unsigned char* p) {
    u64 v;
    std::memcpy(&v, p, 8);
    return __builtin_bswap64(v);
}

struct Sha512 {
    u64 h[8];
    unsigned char buf[128];
    size_t fill;
    u64 total;      // bytes; messages here are far under 2^61

    void init() {
        static const u64 iv[8] = {
            0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL,
            0x3c6ef372fe94f82bULL, 0xa54ff53a5f1d36f1ULL,
            0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
            0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
        std::memcpy(h, iv, sizeof iv);
        fill = 0;
        total = 0;
    }

    void block(const unsigned char* p) {
        u64 w[80];
        for (int t = 0; t < 16; ++t) w[t] = load_be64(p + 8 * t);
        for (int t = 16; t < 80; ++t) {
            u64 s0 = rotr(w[t - 15], 1) ^ rotr(w[t - 15], 8) ^ (w[t - 15] >> 7);
            u64 s1 = rotr(w[t - 2], 19) ^ rotr(w[t - 2], 61) ^ (w[t - 2] >> 6);
            w[t] = w[t - 16] + s0 + w[t - 7] + s1;
        }
        u64 a = h[0], b = h[1], c = h[2], d = h[3];
        u64 e = h[4], f = h[5], g = h[6], hh = h[7];
        for (int t = 0; t < 80; ++t) {
            u64 t1 = hh + (rotr(e, 14) ^ rotr(e, 18) ^ rotr(e, 41)) +
                     ((e & f) ^ (~e & g)) + K512[t] + w[t];
            u64 t2 = (rotr(a, 28) ^ rotr(a, 34) ^ rotr(a, 39)) +
                     ((a & b) ^ (a & c) ^ (b & c));
            hh = g; g = f; f = e; e = d + t1;
            d = c; c = b; b = a; a = t1 + t2;
        }
        h[0] += a; h[1] += b; h[2] += c; h[3] += d;
        h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
    }

    void update(const unsigned char* p, size_t n) {
        total += n;
        if (fill) {
            size_t take = 128 - fill < n ? 128 - fill : n;
            std::memcpy(buf + fill, p, take);
            fill += take; p += take; n -= take;
            if (fill < 128) return;
            block(buf);
            fill = 0;
        }
        for (; n >= 128; p += 128, n -= 128) block(p);
        if (n) {
            std::memcpy(buf, p, n);
            fill = n;
        }
    }

    void final(unsigned char out[64]) {
        u64 bits = total * 8;
        buf[fill++] = 0x80;
        if (fill > 112) {
            std::memset(buf + fill, 0, 128 - fill);
            block(buf);
            fill = 0;
        }
        std::memset(buf + fill, 0, 120 - fill);   // high length word: 0
        u64 be = __builtin_bswap64(bits);
        std::memcpy(buf + 120, &be, 8);
        block(buf);
        for (int i = 0; i < 8; ++i) {
            u64 v = __builtin_bswap64(h[i]);
            std::memcpy(out + 8 * i, &v, 8);
        }
    }
};

// -- scalars mod L, little-endian 64-bit limbs -------------------------

// L = 2^252 + 27742317777372353535851937790883648493
const u64 L_[4] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL,
                   0x0000000000000000ULL, 0x1000000000000000ULL};
// floor(2^512 / L), 261 bits
const u64 MU_[5] = {0xed9ce5a30a2c131bULL, 0x2106215d086329a7ULL,
                    0xffffffffffffffebULL, 0xffffffffffffffffULL,
                    0x000000000000000fULL};

inline bool lt_l(const u64 s[4]) {
    for (int i = 3; i >= 0; --i) {
        if (s[i] < L_[i]) return true;
        if (s[i] > L_[i]) return false;
    }
    return false;
}

// r = x mod L for any x below 2^512: Barrett, HAC 14.42 with b = 2^64
// and k = 4 (at most two subtractions at the end)
void mod_l(const u64 x[8], u64 r[4]) {
    u64 q2[10] = {0};
    for (int i = 0; i < 5; ++i) {            // (x >> 192) * MU
        u64 carry = 0;
        for (int j = 0; j < 5; ++j) {
            u128 t = (u128)x[3 + i] * MU_[j] + q2[i + j] + carry;
            q2[i + j] = (u64)t;
            carry = (u64)(t >> 64);
        }
        q2[i + 5] = carry;
    }
    const u64* q3 = q2 + 5;                  // >> 320
    u64 r2[5] = {0};                         // q3 * L mod 2^320
    for (int i = 0; i < 5; ++i) {
        u64 carry = 0;
        for (int j = 0; j < 4 && i + j < 5; ++j) {
            u128 t = (u128)q3[i] * L_[j] + r2[i + j] + carry;
            r2[i + j] = (u64)t;
            carry = (u64)(t >> 64);
        }
        if (i + 4 < 5) r2[i + 4] += carry;
    }
    u64 d[5];                                // x - r2 mod 2^320
    u64 borrow = 0;
    for (int i = 0; i < 5; ++i) {
        u128 t = (u128)x[i] - r2[i] - borrow;
        d[i] = (u64)t;
        borrow = (u64)(t >> 64) & 1;
    }
    for (;;) {                               // while d >= L: d -= L
        bool ge = d[4] != 0;
        if (!ge) ge = !lt_l(d);
        if (!ge) break;
        borrow = 0;
        for (int i = 0; i < 5; ++i) {
            u128 t = (u128)d[i] - (i < 4 ? L_[i] : 0) - borrow;
            d[i] = (u64)t;
            borrow = (u64)(t >> 64) & 1;
        }
    }
    std::memcpy(r, d, 32);
}

// acc (8 limbs) += z (2 limbs) * v (4 limbs); z*v is under 2^384 and a
// batch has under 2^32 terms, so 512 bits never overflow
inline void muladd(u64 acc[8], const u64 z[2], const u64 v[4]) {
    u64 p[6] = {0};
    for (int i = 0; i < 2; ++i) {
        u64 carry = 0;
        for (int j = 0; j < 4; ++j) {
            u128 t = (u128)z[i] * v[j] + p[i + j] + carry;
            p[i + j] = (u64)t;
            carry = (u64)(t >> 64);
        }
        p[i + 4] = carry;
    }
    u64 carry = 0;
    for (int i = 0; i < 8; ++i) {
        u128 t = (u128)acc[i] + (i < 6 ? p[i] : 0) + carry;
        acc[i] = (u64)t;
        carry = (u64)(t >> 64);
    }
}

// -- signed radix-32 recode --------------------------------------------

// sum_j 16 * 32^j: with it added, the signed digits of x are the plain
// base-32 digits less 16 (crypto/ed25519._recode_w5's bias trick)
const u64 BIAS_[5] = {0x0842108421084210ULL, 0x1084210842108421ULL,
                      0x2108421084210842ULL, 0x4210842108421084ULL,
                      0x0000000000000008ULL};

// the ndig signed digits of x (nlimb limbs, below 2^(5*ndig - 1)),
// least significant first
inline void recode(const u64* x, int nlimb, int ndig, int8_t* digs) {
    u64 b[6] = {0};
    // the bias of ndig digits is BIAS_ cut to 5*ndig bits
    int top = 5 * ndig;
    u64 carry = 0;
    for (int i = 0; i < 5; ++i) {
        u64 bias = BIAS_[i];
        int lo = 64 * i;
        if (lo >= top) bias = 0;
        else if (lo + 64 > top) bias &= (((u64)1) << (top - lo)) - 1;
        u128 t = (u128)(i < nlimb ? x[i] : 0) + bias + carry;
        b[i] = (u64)t;
        carry = (u64)(t >> 64);
    }
    for (int j = 0; j < ndig; ++j) {
        int off = 5 * j, k = off >> 6, sh = off & 63;
        u64 word = b[k] >> sh;
        if (sh > 59) word |= b[k + 1] << (64 - sh);
        digs[j] = (int8_t)((int)(word & 31) - 16);
    }
}

// A block of columns at a time: the arrays are (rows, width) with the
// batch in the minor dimension, so a column written alone touches a
// page a row; BLK columns are gathered first and each row then gets
// BLK consecutive elements.
const int BLK = 64;

struct Block {
    int8_t digs[BLK][52];
    uint32_t words[BLK][8];
    int fill;
};

// columns [col0, col0 + fill) of the MSB-first (ndig, width) digit
// arrays and of the (8, width) word array
inline void flush(Block& blk, int ndig, long width, long col0,
                  uint32_t* words, int32_t* mag, unsigned char* neg) {
    for (int j = 0; j < ndig; ++j) {
        long at = (long)(ndig - 1 - j) * width + col0;
        for (int b = 0; b < blk.fill; ++b) {
            int d = blk.digs[b][j];
            mag[at + b] = d < 0 ? -d : d;
            neg[at + b] = d < 0;
        }
    }
    for (int w = 0; w < 8; ++w)
        for (int b = 0; b < blk.fill; ++b)
            words[(long)w * width + col0 + b] = blk.words[b][w];
    blk.fill = 0;
}

// -- the base point, compressed ----------------------------------------

inline void base_words(uint32_t w[8], bool negated) {
    unsigned char enc[32];
    std::memset(enc, 0x66, 32);
    enc[0] = 0x58;
    if (negated) enc[31] ^= 0x80;
    std::memcpy(w, enc, 32);
}

inline u64 key_hash(const unsigned char* k) {
    u64 w[4];
    std::memcpy(w, k, 32);
    u64 x = w[0] ^ rotr(w[1], 17) ^ rotr(w[2], 31) ^ rotr(w[3], 47);
    x ^= x >> 33; x *= 0xff51afd7ed558ccdULL;
    x ^= x >> 33; x *= 0xc4ceb9fe1a85ec53ULL;
    x ^= x >> 33;
    return x;
}

}  // namespace

extern "C" {

// Packs n signatures.  keys: n*32 bytes, sigs: n*64, msgs: the messages
// end to end, message i of mlens[i] bytes; zblock: n*16 random bytes.  kw / nw are the padded widths the caller chose (nw >= n, kw
// >= 1 + distinct keys).  Every element of the six outputs is written:
// a_words (8, kw) and r_words (8, nw) uint32; a_mag (52, kw), r_mag
// (26, nw) int32; a_neg, r_neg the same shapes, one byte each.
// Returns the number of distinct keys; -1 where a signature's s is not
// below L (the caller's structural reject); -2 on widths too small;
// -3 where memory could not be had.
long rlc_pack(long n, const unsigned char* keys, const unsigned char* sigs,
              const unsigned char* msgs, const int64_t* mlens,
              const unsigned char* zblock, long kw, long nw,
              uint32_t* a_words, uint32_t* r_words,
              int32_t* a_mag, unsigned char* a_neg,
              int32_t* r_mag, unsigned char* r_neg) {
    if (n < 1 || nw < n || kw < 2) return -2;
    for (long i = 0; i < n; ++i) {
        u64 s[4];
        std::memcpy(s, sigs + 64 * i + 32, 32);
        if (!lt_l(s)) return -1;
    }

    long cap = 16;
    while (cap < 2 * n) cap <<= 1;
    // table: slot of a key or -1; first[slot]: the index it was first
    // seen at; acc[slot]: its running sum, acc[n] the sum for c
    int32_t* table = (int32_t*)std::malloc(sizeof(int32_t) * cap);
    int32_t* first = (int32_t*)std::malloc(sizeof(int32_t) * n);
    u64* acc = (u64*)std::calloc((size_t)(n + 1) * 8, sizeof(u64));
    if (!table || !first || !acc) {
        std::free(table); std::free(first); std::free(acc);
        return -3;
    }
    std::memset(table, 0xff, sizeof(int32_t) * cap);
    std::memset(r_mag, 0, sizeof(int32_t) * 26 * nw);
    std::memset(r_neg, 0, (size_t)26 * nw);

    long nkeys = 0;
    u64* acc_c = acc + (size_t)n * 8;
    Block blk;
    blk.fill = 0;
    for (long i = 0; i < n; ++i) {
        const unsigned char* key = keys + 32 * i;
        const unsigned char* sig = sigs + 64 * i;
        unsigned char digest[64];
        Sha512 sh;
        sh.init();
        sh.update(sig, 32);
        sh.update(key, 32);
        sh.update(msgs, (size_t)mlens[i]);
        msgs += mlens[i];
        sh.final(digest);
        u64 wide[8], h[4], s[4], z[2];
        std::memcpy(wide, digest, 64);
        mod_l(wide, h);
        std::memcpy(s, sig + 32, 32);
        std::memcpy(z, zblock + 16 * i, 16);
        z[1] |= ((u64)1) << 63;

        u64 at = key_hash(key) & (u64)(cap - 1);
        long slot;
        for (;;) {
            slot = table[at];
            if (slot < 0) {
                slot = nkeys++;
                table[at] = (int32_t)slot;
                first[slot] = (int32_t)i;
                break;
            }
            if (!std::memcmp(keys + 32 * (long)first[slot], key, 32)) break;
            at = (at + 1) & (u64)(cap - 1);
        }
        muladd(acc + (size_t)slot * 8, z, h);
        muladd(acc_c, z, s);

        std::memcpy(blk.words[blk.fill], sig, 32);
        recode(z, 2, 26, blk.digs[blk.fill]);
        if (++blk.fill == BLK) flush(blk, 26, nw, i + 1 - BLK, r_words, r_mag, r_neg);
    }
    flush(blk, 26, nw, n - blk.fill, r_words, r_mag, r_neg);

    long rc = nkeys;
    if (1 + nkeys > kw) {
        rc = -2;
    } else {
        uint32_t filler[8], negb[8];
        base_words(filler, false);
        base_words(negb, true);
        for (int w = 0; w < 8; ++w)
            for (long i = n; i < nw; ++i) r_words[(long)w * nw + i] = filler[w];
        std::memset(a_mag, 0, sizeof(int32_t) * 52 * kw);
        std::memset(a_neg, 0, (size_t)52 * kw);
        for (int w = 0; w < 8; ++w)
            for (long j = 1 + nkeys; j < kw; ++j) a_words[(long)w * kw + j] = filler[w];
        for (long j = 0; j <= nkeys; ++j) {     // slot 0 is (-B, c)
            u64 sc[4];
            mod_l(j == 0 ? acc_c : acc + (size_t)(j - 1) * 8, sc);
            if (j == 0) std::memcpy(blk.words[blk.fill], negb, 32);
            else std::memcpy(blk.words[blk.fill], keys + 32 * (long)first[j - 1], 32);
            recode(sc, 4, 52, blk.digs[blk.fill]);
            if (++blk.fill == BLK) flush(blk, 52, kw, j + 1 - BLK, a_words, a_mag, a_neg);
        }
        flush(blk, 52, kw, 1 + nkeys - blk.fill, a_words, a_mag, a_neg);
    }
    std::free(table); std::free(first); std::free(acc);
    return rc;
}

void rlc_sha512(const unsigned char* msg, long len, unsigned char out[64]) {
    Sha512 sh;
    sh.init();
    sh.update(msg, (size_t)len);
    sh.final(out);
}

// 64 little-endian bytes mod L, as 32
void rlc_sc_reduce(const unsigned char in[64], unsigned char out[32]) {
    u64 x[8], r[4];
    std::memcpy(x, in, 64);
    mod_l(x, r);
    std::memcpy(out, r, 32);
}

// 0 when SHA-512("abc") and the reduction of 2^512 - 1 come out right
// (a library built for another byte order or by a broken compiler must
// not pack a single batch)
int rlc_selftest() {
    static const unsigned char abc[64] = {
        0xdd, 0xaf, 0x35, 0xa1, 0x93, 0x61, 0x7a, 0xba, 0xcc, 0x41, 0x73,
        0x49, 0xae, 0x20, 0x41, 0x31, 0x12, 0xe6, 0xfa, 0x4e, 0x89, 0xa9,
        0x7e, 0xa2, 0x0a, 0x9e, 0xee, 0xe6, 0x4b, 0x55, 0xd3, 0x9a, 0x21,
        0x92, 0x99, 0x2a, 0x27, 0x4f, 0xc1, 0xa8, 0x36, 0xba, 0x3c, 0x23,
        0xa3, 0xfe, 0xeb, 0xbd, 0x45, 0x4d, 0x44, 0x23, 0x64, 0x3c, 0xe8,
        0x0e, 0x2a, 0x9a, 0xc9, 0x4f, 0xa5, 0x4c, 0xa4, 0x9f};
    static const unsigned char ff_mod_l[32] = {
        0x00, 0x0f, 0x9c, 0x44, 0xe3, 0x11, 0x06, 0xa4, 0x47, 0x93, 0x85,
        0x68, 0xa7, 0x1b, 0x0e, 0xd0, 0x65, 0xbe, 0xf5, 0x17, 0xd2, 0x73,
        0xec, 0xce, 0x3d, 0x9a, 0x30, 0x7c, 0x1b, 0x41, 0x99, 0x03};
    unsigned char out[64], ff[64];
    rlc_sha512((const unsigned char*)"abc", 3, out);
    if (std::memcmp(out, abc, 64)) return 1;
    std::memset(ff, 0xff, 64);
    rlc_sc_reduce(ff, out);
    if (std::memcmp(out, ff_mod_l, 32)) return 2;
    return 0;
}

}  // extern "C"
