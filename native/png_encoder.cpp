// Native PNG encoder for the serving path.
//
// Every image leaves this framework as a base64 PNG (the reference's wire
// format: /root/reference/scripts/spartan/worker.py:45-48 pil_to_64,
// decoded at distributed.py:103-106). The encode runs after the TPU has
// finished, with the device idle and the request's client waiting, and one
// deflate at level 6 over a 1024x1024 image is 0.1 s on one core. So the
// image is deflated as K strips of whole scanlines on K threads and
// stitched into ONE zlib stream (pigz's construction): every strip is raw
// deflate at the caller's level, started from the 32 KiB of scanlines
// before it as its dictionary, ended by a sync flush (the last by the final
// block); then the Adler-32 of all scanlines, combined from the strips'
// own. Filter 0, RGB8/RGBA8, one IDAT: a decoder sees the pixels and the
// level it saw before, and at K = 1 the file is byte for byte what
// compress2 gave.
//
// K is read from what the encoder can see: the scanline bytes of this image
// (STRIP_FLOOR a strip, so a thumbnail stays one strip on the calling
// thread) and the cores this process may run on, under STRIP_CAP. PERF.md
// section 6 (PR 32) has the scaling that chose both. Loaded via ctypes
// (runtime/native.py), falling back to PIL when the toolchain is
// unavailable.
//
// Build: g++ -O3 -shared -fPIC -pthread png_encoder.cpp -lz -o libsdtpu_png.so

#include <algorithm>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <vector>
#include <pthread.h>
#include <sched.h>
#include <zlib.h>

namespace {

constexpr size_t STRIP_FLOOR = 48 * 1024;   // scanline bytes a strip, least
constexpr int STRIP_CAP = 8;                // strips (threads) an image, most
constexpr size_t WINDOW = 32 * 1024;        // deflate's reach backwards
constexpr size_t FLUSH_SLACK = 64;          // a sync flush past compressBound

// the CPUs of a mask but one, in order
std::vector<int> cpus_but(const cpu_set_t& set, int but) {
    std::vector<int> cpus;
    for (int c = 0; c < CPU_SETSIZE; ++c)
        if (c != but && CPU_ISSET(c, &set)) cpus.push_back(c);
    return cpus;
}

int plan_strips(size_t scanline_bytes, int height, size_t cores) {
    size_t k = std::min<size_t>(scanline_bytes / STRIP_FLOOR, STRIP_CAP);
    k = std::min(k, cores);
    k = std::min(k, static_cast<size_t>(height));
    return static_cast<int>(std::max<size_t>(k, 1));
}

void put_be32(uint8_t* at, uint32_t v) {
    at[0] = (v >> 24) & 0xff; at[1] = (v >> 16) & 0xff;
    at[2] = (v >> 8) & 0xff;  at[3] = v & 0xff;
}

// a whole chunk whose data is already at `at + 8`
uint8_t* close_chunk(uint8_t* at, const char type[4], size_t len) {
    put_be32(at, static_cast<uint32_t>(len));
    std::memcpy(at + 4, type, 4);
    put_be32(at + 8 + len,
             crc32(crc32(0L, Z_NULL, 0), at + 4, static_cast<uInt>(4 + len)));
    return at + 12 + len;
}

// the two bytes deflateInit(level) opens a zlib stream with
uint16_t zlib_header(int level) {
    if (level == Z_DEFAULT_COMPRESSION) level = 6;
    unsigned flevel = level < 2 ? 0 : level < 6 ? 1 : level == 6 ? 2 : 3;
    unsigned header = ((Z_DEFLATED + (7 << 4)) << 8) | (flevel << 6);
    return static_cast<uint16_t>(header + 31 - header % 31);
}

// The pixels as the caller holds them: any strides, in bytes. A TPU hands
// the host a decoded image as three planes (channel the slowest axis), and
// a crop is a view with the wider image's row stride; gathering either into
// scanlines here, a strip a thread, takes the serial copy
// (numpy.ascontiguousarray: 6-9 ms for 1024x1024 planes) off the caller.
struct Image {
    const uint8_t* pixels;
    int width, channels;
    ptrdiff_t row_stride, col_stride, chan_stride;

    void scanline(int y, uint8_t* at) const {
        const uint8_t* row = pixels + row_stride * y;
        at[0] = 0;                                      // filter: None
        if (chan_stride == 1 && col_stride == channels) {
            std::memcpy(at + 1, row, static_cast<size_t>(width) * channels);
            return;
        }
        for (int c = 0; c < channels; ++c) {
            const uint8_t* from = row + chan_stride * c;
            uint8_t* to = at + 1 + c;
            for (int x = 0; x < width; ++x)
                to[static_cast<ptrdiff_t>(x) * channels] = from[col_stride * x];
        }
    }
};

struct Strip {
    int row_lo = 0, row_hi = 0;
    uint8_t* out = nullptr;     // its slot in the caller's buffer
    size_t cap = 0, len = 0;
    uLong adler = 0, crc = 0;
    bool ok = false;
};

struct Job {
    Strip* strip;
    const Image* image;
    int level;
    bool last;                  // the image's last strip ends the stream
    const cpu_set_t* roam;      // the mask its thread takes back once running
};

void deflate_strip(const Job& job) {
    Strip& s = *job.strip;
    const Image& image = *job.image;
    try {
        // this strip's scanlines, after as many rows as hold its dictionary
        const size_t line = static_cast<size_t>(image.width) * image.channels
            + 1;
        const int back = static_cast<int>((WINDOW + line - 1) / line);
        const int first = std::max(0, s.row_lo - back);
        std::vector<uint8_t> raw(line * (s.row_hi - first));
        for (int y = first; y < s.row_hi; ++y)
            image.scanline(y, raw.data() + line * (y - first));
        const size_t own = line * (s.row_lo - first);
        const size_t dict = std::min(own, WINDOW);

        z_stream z;
        std::memset(&z, 0, sizeof(z));
        if (deflateInit2(&z, job.level, Z_DEFLATED, -15, 8,
                         Z_DEFAULT_STRATEGY) != Z_OK)
            return;
        bool fed = !dict || deflateSetDictionary(
            &z, raw.data() + own - dict, static_cast<uInt>(dict)) == Z_OK;
        z.next_in = raw.data() + own;
        z.avail_in = static_cast<uInt>(raw.size() - own);
        z.next_out = s.out;
        z.avail_out = static_cast<uInt>(s.cap);
        const int rc = fed ? deflate(&z, job.last ? Z_FINISH : Z_SYNC_FLUSH)
                           : Z_STREAM_ERROR;
        s.ok = (job.last ? rc == Z_STREAM_END : rc == Z_OK)
            && z.avail_in == 0 && z.avail_out > 0;
        s.len = s.cap - z.avail_out;
        deflateEnd(&z);
        if (!s.ok) return;
        s.adler = adler32(adler32(0L, Z_NULL, 0), raw.data() + own,
                          static_cast<uInt>(raw.size() - own));
        s.crc = crc32(crc32(0L, Z_NULL, 0), s.out, static_cast<uInt>(s.len));
    } catch (...) {             // out of memory: the caller falls back
        s.ok = false;
    }
}

void* run_job(void* arg) {
    const Job& job = *static_cast<const Job*>(arg);
    sched_setaffinity(0, sizeof(cpu_set_t), job.roam);
    deflate_strip(job);
    return nullptr;
}

// A thread for `job` that starts on `cpu`. Left to the kernel, a new thread
// is queued behind its parent, which goes on to its own strip and is not
// preempted before the next tick, and a thread that never sleeps then stays
// where it is until the balancer's next pass: on the hosts measured, both
// longer than a strip takes. So the thread is created with one CPU to run
// on, and takes the caller's mask back (`roam`) as its first act: the
// scheduler has its say again from there.
bool start_on(int cpu, Job* job, pthread_t* thread) {
    pthread_attr_t attr;
    if (pthread_attr_init(&attr) != 0) return false;
    cpu_set_t one;
    CPU_ZERO(&one);
    CPU_SET(cpu, &one);
    bool ok = pthread_attr_setaffinity_np(&attr, sizeof(one), &one) == 0
        && pthread_create(thread, &attr, run_job, job) == 0;
    pthread_attr_destroy(&attr);
    return ok;
}

}  // namespace

extern "C" {

// Encode HxW pixels with `channels` (3=RGB, 4=RGBA) 8-bit samples, held
// with the three strides given in bytes (a C-contiguous array has
// width*channels, channels, 1). Returns the number of bytes written to
// `out` (capacity `out_cap`), 0 on failure, or the required capacity as a
// negative number if `out` is too small. `strips_used`, if given, receives
// the K the image was deflated as.
long sdtpu_encode_png(const uint8_t* pixels, int width, int height,
                      int channels, ptrdiff_t row_stride,
                      ptrdiff_t col_stride, ptrdiff_t chan_stride,
                      int compression_level, uint8_t* out, long out_cap,
                      int* strips_used) {
    if (width <= 0 || height <= 0 || (channels != 3 && channels != 4))
        return 0;
    const Image image = {pixels, width, channels, row_stride, col_stride,
                         chan_stride};
    const size_t line = static_cast<size_t>(width) * channels + 1;
    if (line * height > 0x7fffffffu)    // zlib counts a call's bytes in 32 bits
        return 0;
    cpu_set_t allowed;          // the CPUs this thread may run on
    if (sched_getaffinity(0, sizeof(allowed), &allowed) != 0)
        CPU_ZERO(&allowed);     // the kernel will not say: one strip
    const int k = plan_strips(line * height, height, CPU_COUNT(&allowed));

    // signature 8, IHDR 25, IDAT length and type 8, zlib header 2; then the
    // strips' slots; then Adler-32 4, IDAT's CRC 4, IEND 12
    const size_t head = 8 + 25 + 8 + 2;
    std::vector<Strip> strips(k);
    std::vector<Job> jobs(k);
    size_t need = head;
    for (int i = 0; i < k; ++i) {
        Strip& s = strips[i];
        s.row_lo = static_cast<int>(static_cast<int64_t>(height) * i / k);
        s.row_hi = static_cast<int>(static_cast<int64_t>(height) * (i + 1) / k);
        s.cap = compressBound(static_cast<uLong>(
            line * (s.row_hi - s.row_lo))) + FLUSH_SLACK;
        s.out = out + need;
        need += s.cap;
        jobs[i] = {&s, &image, compression_level, i == k - 1, &allowed};
    }
    need += 4 + 4 + 12;
    if (static_cast<long>(need) > out_cap)
        return -static_cast<long>(need);

    // strip 0 stays with the caller, the others take the other CPUs in turn
    const std::vector<int> others = cpus_but(allowed, sched_getcpu());
    std::vector<pthread_t> threads;
    threads.reserve(k);
    for (int i = 1; i < k; ++i) {
        pthread_t thread;
        if (start_on(others[(i - 1) % others.size()], &jobs[i], &thread))
            threads.push_back(thread);
        else                    // no thread to be had: this one does it
            deflate_strip(jobs[i]);
    }
    deflate_strip(jobs[0]);
    for (pthread_t thread : threads) pthread_join(thread, nullptr);
    for (const Strip& s : strips)
        if (!s.ok) return 0;

    static const uint8_t sig[8] = {0x89, 'P', 'N', 'G', '\r', '\n', 0x1a,
                                   '\n'};
    std::memcpy(out, sig, 8);
    uint8_t* ihdr = out + 8;
    put_be32(ihdr + 8, static_cast<uint32_t>(width));
    put_be32(ihdr + 12, static_cast<uint32_t>(height));
    ihdr[16] = 8;                              // bit depth
    ihdr[17] = (channels == 3) ? 2 : 6;        // color type: RGB / RGBA
    ihdr[18] = 0; ihdr[19] = 0; ihdr[20] = 0;  // deflate/adaptive/no-interlace
    uint8_t* idat = close_chunk(ihdr, "IHDR", 13);

    // IDAT: the strips closed up behind the first, one CRC from their own
    std::memcpy(idat + 4, "IDAT", 4);
    const uint16_t header = zlib_header(compression_level);
    idat[8] = header >> 8;
    idat[9] = header & 0xff;
    uLong crc = crc32(crc32(0L, Z_NULL, 0), idat + 4, 6);
    uLong adler = adler32(0L, Z_NULL, 0);
    uint8_t* end = idat + 10;
    for (const Strip& s : strips) {
        if (s.out != end) std::memmove(end, s.out, s.len);
        end += s.len;
        crc = crc32_combine(crc, s.crc, static_cast<z_off_t>(s.len));
        adler = adler32_combine(adler, s.adler, static_cast<z_off_t>(
            line * (s.row_hi - s.row_lo)));
    }
    put_be32(end, static_cast<uint32_t>(adler));
    crc = crc32(crc, end, 4);
    end += 4;
    put_be32(idat, static_cast<uint32_t>(end - (idat + 8)));
    put_be32(end, static_cast<uint32_t>(crc));
    end = close_chunk(end + 4, "IEND", 0);
    if (strips_used) *strips_used = k;
    return static_cast<long>(end - out);
}

}  // extern "C"
